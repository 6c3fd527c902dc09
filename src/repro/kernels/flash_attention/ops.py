"""Jit'd public wrapper for the flash attention kernel.

Model-layout API: q (B, Sq, H, hd), k/v (B, Sk, Hkv, hd) — reshaped to the
kernel's (B*H, S, hd) layout.  On the TPU it compiles via Mosaic; on
the CPU it runs in interpret mode (the kernel body runs in Python) so the
same code path is exercised in tests.  Other backends raise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._backend import interpret_mode

from .kernel import flash_attention_bhsd, paged_decode_attention_hp


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Paged single-token decode attention (vLLM-style): attend one query
    per sequence through a page table instead of a dense (B, C, ...)
    cache slab.

    Model-layout API matching the serving page pools: q (B, 1, H, hd) —
    the current token; k_pages/v_pages (P, ps, Hkv, hd) — one layer's
    page pool from `models.api.init_paged_cache` (page 0 reserved as the
    never-read null page); tables (B, n_pages_per_slot) int32 physical
    page ids; lengths (B,) int32 live tokens per slot INCLUDING the
    current token (whose k/v must already be scattered into the pages).
    Returns (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    kp = k_pages.transpose(2, 0, 1, 3)   # (Hkv, P, ps, hd)
    vp = v_pages.transpose(2, 0, 1, 3)
    it = interpret_mode(interpret)
    out = paged_decode_attention_hp(
        q[:, 0], kp, vp, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), interpret=it)
    return out[:, None]
