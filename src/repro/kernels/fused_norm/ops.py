"""Jit'd public wrappers for the fused RMSNorm(+residual) kernel.

Model-layout API: x (and res) are (..., d) — leading dims are flattened
into one token axis for the kernel.  On the TPU it compiles via Mosaic; on
the CPU it runs in interpret mode (the kernel body runs in Python) so the
same code path is exercised in tests.  Other backends raise.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels._backend import interpret_mode

from .kernel import fused_rmsnorm_pallas, fused_rmsnorm_residual_pallas


@functools.partial(jax.jit, static_argnames=("eps", "bt", "interpret"))
def fused_rmsnorm(
    x, scale, *, eps: float = 1e-6, bt: int = 256, interpret: bool | None = None
):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    it = interpret_mode(interpret)
    out = fused_rmsnorm_pallas(xf, scale, eps=eps, bt=bt, interpret=it)
    return out.reshape(*lead, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("eps", "bt", "interpret"))
def fused_rmsnorm_residual(
    x, res, scale, *, eps: float = 1e-6, bt: int = 256, interpret: bool | None = None
):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    rf = res.reshape(-1, res.shape[-1])
    it = interpret_mode(interpret)
    s, out = fused_rmsnorm_residual_pallas(
        xf, rf, scale, eps=eps, bt=bt, interpret=it
    )
    return s.reshape(*lead, x.shape[-1]), out.reshape(*lead, x.shape[-1])
