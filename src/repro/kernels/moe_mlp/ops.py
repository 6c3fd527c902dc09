"""Jit'd wrapper for the fused grouped expert-MLP kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels._backend import interpret_mode

from .kernel import moe_mlp_pallas


@functools.partial(jax.jit, static_argnames=("swiglu", "bt", "bf",
                                             "interpret"))
def moe_mlp(x, wg, wi, wo, *, swiglu: bool = True, bt: int = 128,
            bf: int = 512, interpret: bool | None = None):
    it = interpret_mode(interpret)
    return moe_mlp_pallas(x, wg, wi, wo, swiglu=swiglu, bt=bt, bf=bf,
                          interpret=it)
