"""Jit'd public wrapper for the fused dense gated-MLP kernel.

Model-layout API: x is (..., d) — leading dims are flattened into one
token axis for the kernel.  On the TPU it compiles via Mosaic; on
the CPU it runs in interpret mode (the kernel body runs in Python) so the
same code path is exercised in tests.  Other backends raise.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels._backend import interpret_mode

from .kernel import fused_mlp_pallas


@functools.partial(jax.jit, static_argnames=("swiglu", "bt", "bf", "interpret"))
def fused_mlp(
    x,
    wg,
    wi,
    wo,
    *,
    swiglu: bool = True,
    bt: int = 128,
    bf: int = 512,
    interpret: bool | None = None,
):
    """wg is only read when swiglu=True; pass None for plain GELU MLPs."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    it = interpret_mode(interpret)
    out = fused_mlp_pallas(xf, wg, wi, wo, swiglu=swiglu, bt=bt, bf=bf, interpret=it)
    return out.reshape(*lead, x.shape[-1])
