"""Jit'd wrapper for the WKV6 kernel (interpret mode on the CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels._backend import interpret_mode

from .kernel import wkv6_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, s0, *, chunk: int = 64,
         interpret: bool | None = None):
    it = interpret_mode(interpret)
    return wkv6_pallas(r, k, v, logw, u, s0, chunk=chunk, interpret=it)
