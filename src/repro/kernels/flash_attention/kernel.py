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
# Paged decode attention: one query token per sequence, KV behind a page
# table.  The page axis is the innermost (sequential) grid dim; each step
# the K/V index_maps dereference `tables[b, p]` — a scalar-prefetch
# lookup, so the DMA engine fetches exactly the pages the slot owns and
# the dense (B, C, ...) cache view is never materialized.  One grid step
# serves all query heads of one kv head, so each page is read once per
# group, and the q/o blocks' last two dims (group, hd) are whole array
# dims, as Mosaic's tiling rule asks.
# ---------------------------------------------------------------------------


def _paged_decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, ps: int,
                         n_pages_per_slot: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    # Block-level skip: pages wholly past the slot's live length hold
    # either stale KV or the null page — no compute, no mask fixups.
    @pl.when(p * ps < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (group, ps)
        s *= 1.0 / math.sqrt(q.shape[-1])
        kpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]                               # (group, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        pr = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + pr.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == n_pages_per_slot - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_hp(q, k_pages, v_pages, tables, lengths, *,
                              interpret: bool = False):
    """Single-token decode attention through a page table.

    q: (B, H, hd) — the current token's queries; k_pages/v_pages:
    (Hkv, P, ps, hd) page pools with H % Hkv == 0 (GQA); tables:
    (B, n_pages_per_slot) int32 physical page ids (0 = null page);
    lengths: (B,) int32 live tokens per slot — the query sits at
    position lengths[b]-1, so causality is just `kpos < length`.
    Returns (B, H, hd)."""
    bsz, h, hd = q.shape
    hkv, _, ps, _ = k_pages.shape
    npp = tables.shape[1]
    group = h // hkv
    # heads [g*group, (g+1)*group) share kv head g
    qg = q.reshape(bsz, hkv, group, hd)

    kernel = functools.partial(_paged_decode_kernel, ps=ps,
                               n_pages_per_slot=npp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, hkv, npp),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd),
                         lambda b, g, p, tbl, ln: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, g, p, tbl, ln: (g, tbl[b, p], 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, g, p, tbl, ln: (g, tbl[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, g, p, tbl, ln: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),    # running max
            pltpu.VMEM((group, 1), jnp.float32),    # running denominator
            pltpu.VMEM((group, hd), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, qg, k_pages, v_pages)
    return out.reshape(bsz, h, hd)
