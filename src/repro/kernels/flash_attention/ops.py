"""Jit'd public wrappers for the attention kernels.

`flash_attention`: model layout q (B, Sq, H, hd), k/v (B, Sk, Hkv, hd),
reshaped to the kernel's (B*H, S, hd) layout.  `paged_decode_attention`:
the serving decode's attention, which reads K/V in place from the page
pool through the page tables (no dense cache view, no grouped-head
repeat).  On the TPU they compile via Mosaic; on the CPU they run in
interpret mode (the kernel body runs in Python) so the same code path is
exercised in tests.  Other backends raise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels._backend import interpret_mode

from .kernel import (flash_attention_bhsd, paged_decode_attention_hp,
                     to_pool_rows)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    _, sk, hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    # (B*H) layout must group query heads of one kv head contiguously:
    # reorder q so head-major grouping matches kv: index = b*H + h where
    # heads h in [g*group, (g+1)*group) share kv head g.  transpose above
    # already yields exactly that layout.
    it = interpret_mode(interpret)
    of = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=it)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


# positions one grid step of the paged decode kernel covers: enough for the
# page DMAs of a block to outlast the step's fixed cost
BLOCK_POSITIONS = 128


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, layer, tables,
                           lengths, *, interpret: bool | None = None
                           ) -> jnp.ndarray:
    """Single-token decode attention read straight from the page pool.

    q (B, H, hd): the current token's queries; k_new/v_new (B, Hkv, hd):
    its keys and values, which are not in the pool yet; k_pool/v_pool
    (L, P, ps, W): every layer's pages in `to_pool_rows`' layout, W =
    `kernel.pool_row_width(Hkv, hd)`, as `models.api.init_paged_cache`
    lays them out (page 0 is the never-read null page); layer: int32
    scalar, the layer to read; tables
    (B, n_pages_per_slot) int32 physical page ids; lengths (B,) int32
    tokens each sequence holds in the pool.  The current token sits at
    position lengths[b] and attends to the pool's positions below it and
    to itself.  Returns (B, H, hd)."""
    b, h, hd = q.shape
    hkv = k_new.shape[1]
    group = h // hkv
    ps, w = k_pool.shape[2:]
    npp = tables.shape[1]
    ppb = max(1, min(BLOCK_POSITIONS // ps, npp))
    tables = jnp.pad(tables, ((0, 0), (0, -npp % ppb)))

    # query head j keeps its vector in kv head j // group's columns
    own = jnp.arange(h)[:, None] // group == jnp.arange(hkv)[None, :]
    qb = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    out = paged_decode_attention_hp(
        to_pool_rows(qb, w), to_pool_rows(k_new[:, None], w),
        to_pool_rows(v_new[:, None], w), k_pool, v_pool,
        layer, tables, lengths, scale=1.0 / math.sqrt(hd),
        pages_per_block=ppb, interpret=interpret_mode(interpret))
    # each head's output sits in its own kv head's columns
    out = out[:, :, :hkv * hd].reshape(b, h, hkv, hd)
    return jnp.sum(jnp.where(own[None, :, :, None], out, 0), axis=2)
