"""Jit'd wrapper for the RG-LRU scan kernel (interpret mode on the CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels._backend import interpret_mode

from .kernel import rglru_scan_pallas


@functools.partial(jax.jit, static_argnames=("bs", "bw", "interpret"))
def rglru_scan(a, b, h0, *, bs: int = 256, bw: int = 128,
               interpret: bool | None = None):
    it = interpret_mode(interpret)
    return rglru_scan_pallas(a, b, h0, bs=bs, bw=bw, interpret=it)
