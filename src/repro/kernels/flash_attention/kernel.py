"""Flash attention Pallas TPU kernel (tensor fusion of QK^T, softmax, PV —
paper technique (2) applied at kernel granularity).

Grid: (batch*q_heads, num_q_blocks, num_kv_blocks) with the kv axis
"arbitrary" (sequential) — running max/denominator live in VMEM scratch
and the output block is finalized on the last kv step.  GQA is handled in
the K/V index_map (query head -> kv head) so grouped KV is never
materialized at H query heads.  Causal and sliding-window masks are
applied with block-level skipping (fully-masked kv blocks do no compute).

Block shapes default to (128, 128): MXU-aligned (multiples of 128 on the
matmul dims) and small enough that q/k/v/acc tiles fit VMEM at hd<=256.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 bq: int, bk: int, sk: int, causal: bool,
                 window: int | None, n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # Block-level skip: no valid (q, k) pair in this tile.
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        s *= 1.0 / math.sqrt(q.shape[-1])
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         window: int | None = None, bq: int = 128,
                         bk: int = 128, interpret: bool = False):
    """q: (BH, Sq, hd); k/v: (BHkv, Sk, hd) with BH % BHkv == 0 (GQA).
    Returns (BH, Sq, hd)."""
    bh, sq, hd = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    bq = min(bq, sq)
    bk = min(bk, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[1] // bq
    nk = k.shape[1] // bk

    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, sk=sk, causal=causal, window=window,
        n_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q.shape[1], hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# Paged decode attention: one query token per sequence, K/V read in place
# from the serving engine's page pool.
#
# The pool holds every layer's pages, (L, P, ps, W): a position's kv heads
# side by side, zero-padded to W lanes (a multiple of 128), as
# `models.api.init_paged_cache` lays it out.  It stays in HBM
# (memory_space ANY) and the layer index arrives by scalar prefetch, so no
# per-layer slice or transpose of the pool is made.  One grid step serves
# one sequence and all of its heads: it walks the sequence's blocks of
# `pages_per_block` pages with double-buffered page DMAs (one page of one
# layer is one contiguous (ps, W) slab) and stops at the block that holds
# the sequence's last position: no block past it is read.
#
# Grouped heads need no repeat.  The queries come head-blocked, (H, W):
# query head h's vector sits in kv head h // group's columns, zeros
# elsewhere, so `q @ k.T` over the whole width is each head's score
# against its own kv head.  `p @ v` gives each head a W-wide row whose own
# kv head's columns hold its output; the wrapper keeps those.  The current
# token's K/V are not in the pool yet: its score starts the online softmax.
# ---------------------------------------------------------------------------


def pool_row_width(kv_heads: int, head_dim: int) -> int:
    """Lanes of one position's K (or V) row in the page pool the paged
    decode reads: every kv head side by side, zero-padded to a multiple
    of the TPU's 128 lanes (the kernel's page DMAs need lane-aligned
    rows)."""
    return -(-kv_heads * head_dim // 128) * 128


def to_pool_rows(x, width: int):
    """(..., kv_heads, hd) K, V or head-blocked queries -> (..., width)
    rows of the pool's layout."""
    flat = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, width - flat.shape[-1])])


def _paged_decode_kernel(layer_ref, tables_ref, lengths_ref, q_ref, kn_ref,
                         vn_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *,
                         scale: float, pages_per_block: int, table_width: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    ps = k_buf.shape[2]
    bt = pages_per_block * ps
    n_blocks = (length + bt - 1) // bt

    def page_copies(blk, slot):
        first = b * table_width + blk * pages_per_block
        copies = []
        for j in range(pages_per_block):
            page = tables_ref[first + j]
            copies.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, j], sems.at[0, slot]))
            copies.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, j], sems.at[1, slot]))
        return copies

    @pl.when(n_blocks > 0)
    def _prefetch_first():
        for c in page_copies(0, 0):
            c.start()

    q = q_ref[...]                                        # (H, W)
    # the current token: score against its own key, weight 1 on its value
    m0 = jnp.sum(q.astype(jnp.float32) * kn_ref[...].astype(jnp.float32),
                 axis=1, keepdims=True) * scale           # (H, 1)
    acc0 = jnp.broadcast_to(vn_ref[...].astype(jnp.float32), q.shape)

    def block(blk, carry):
        m_prev, l_prev, acc_prev = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch_next():
            for c in page_copies(blk + 1, 1 - slot):
                c.start()

        for c in page_copies(blk, slot):
            c.wait()
        k = k_buf[slot].reshape(bt, k_buf.shape[3])       # (bt, W)
        v = v_buf[slot].reshape(bt, v_buf.shape[3])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, bt)
        kpos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block, (m0, jnp.ones_like(m0), acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention_hp(q_blocked, k_new, v_new, k_pool, v_pool, layer,
                              tables, lengths, *, scale: float,
                              pages_per_block: int, interpret: bool = False):
    """Single-token decode attention over one layer of the page pool.

    q_blocked: (B, H, W) head-blocked queries (query head h's vector in
    the columns of its kv head, zeros elsewhere); k_new/v_new: (B, 1, W)
    the current token's keys and values, all kv heads side by side;
    k_pool/v_pool: (L, P, ps, W) every layer's pages (page 0 is the null
    page); layer: int32 scalar; tables: (B, npp) int32 physical page ids,
    npp a multiple of `pages_per_block`; lengths: (B,) int32 tokens each
    sequence holds in the pool, which the current token follows.
    Returns (B, H, W): row h holds query head h's output in its kv head's
    columns."""
    bsz, h, w = q_blocked.shape
    _, _, ps, _ = k_pool.shape
    table_width = tables.shape[1]
    if table_width % pages_per_block:
        raise ValueError(f"{table_width} table entries do not split into "
                         f"blocks of {pages_per_block} pages")
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, pages_per_block=pages_per_block,
        table_width=table_width)
    row = lambda b, *_: (b, 0, 0)  # noqa: E731
    buf = pltpu.VMEM((2, pages_per_block, ps, w), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((None, h, w), row),
            pl.BlockSpec((None, 1, w), row),
            pl.BlockSpec((None, 1, w), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, h, w), row),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, w), q_blocked.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q_blocked, k_new, v_new, k_pool, v_pool)
