"""RG-LRU diagonal linear recurrence as a Pallas TPU kernel.

    h_t = a_t * h_{t-1} + b_t        (elementwise over the channel dim)

Grid: (batch, channel_blocks, time_blocks); the time axis is sequential
("arbitrary") and the running hidden state lives in VMEM scratch, so HBM
traffic is exactly one read of (a, b) and one write of h — the recurrence
itself never round-trips.  Channel blocks are 128-lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(h0_ref, a_ref, b_ref, o_ref, h_ref, *, bs: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)        # (1, bw)

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :].astype(jnp.float32) * h \
            + b_ref[0, row, :].astype(jnp.float32)
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, h_ref[...])
    h_ref[...] = h


def rglru_scan_pallas(a, b, h0, *, bs: int = 256, bw: int = 128,
                      interpret: bool = False):
    """a, b: (B, S, W); h0: (B, W). Returns h: (B, S, W)."""
    bsz, s, w = a.shape
    bs = min(bs, s)
    bw = min(bw, w)
    pad_s = (-s) % bs
    pad_w = (-w) % bw
    if pad_s or pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    ns, nw = a.shape[1] // bs, a.shape[2] // bw
    h0 = h0[:, None, :]     # (B, 1, W): a block's last two dims (1, bw)

    out = pl.pallas_call(
        functools.partial(_rglru_kernel, bs=bs),
        grid=(bsz, nw, ns),
        in_specs=[
            pl.BlockSpec((1, 1, bw), lambda ib, iw, it: (ib, 0, iw)),
            pl.BlockSpec((1, bs, bw), lambda ib, iw, it: (ib, it, iw)),
            pl.BlockSpec((1, bs, bw), lambda ib, iw, it: (ib, it, iw)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda ib, iw, it: (ib, it, iw)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(h0, a, b)
    return out[:, :s, :w]
