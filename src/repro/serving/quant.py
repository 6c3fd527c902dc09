"""Int8 KV-cache quantization for the paged serving pool.

KV pages are stored as int8 with one float32 scale per (layer, page,
kv-head): a page leaf `(L, P, ps, kvh, hd)` carries scales
`(L, P, 1, kvh, 1)` (MLA latents `(L, P, ps, D)` carry `(L, P, 1, 1)` —
no head dim to resolve).  Symmetric absmax quantization:

    scale = max(|x|) / 127   over the page's positions and head_dim
    q     = clip(round(x / scale), -127, 127)   (int8)
    x'    = q * scale

so the same HBM holds ~4x the KV bytes (scales are ~1/(2*page_size*hd)
overhead).  The paged gather/scatter round-trips through these helpers:
gather dequantizes pages into the f32 dense sub-cache the unchanged
decode math runs over, scatter re-quantizes with FRESH per-page scales —
stale scales never linger, and a page whose absmax shrinks regains
precision.

Per-page scales only work because the ragged prefill scatter zeroes pad
positions (`paged.paged_prefill_fn`): garbage in a page's tail would
inflate its absmax and crush the real tokens' resolution to ~0.

Everything here is pure `jax.numpy` and shape-polymorphic over the page
axis, so the same helpers serve the pool layout `(L, P, ps, ...)` and
the gathered block layout `(L, n, npp, ps, ...)`; all are traceable
inside the jitted paged prefill/decode builders.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
# floor for the absmax scale: an all-zero page quantizes to zeros instead
# of dividing by zero, and dequantizes back to exact zeros
SCALE_FLOOR = 1e-8


def _reduce_axes(ndim: int, ps_axis: int) -> tuple[int, int]:
    """Scales reduce over the page's position axis and the trailing
    feature axis (head_dim, or the MLA latent dim), keeping the kv-head
    axis (when present) — "per-head scales"."""
    return (ps_axis, ndim - 1)


def page_scales(x, ps_axis: int):
    """Per-(page, head) absmax/127 scales for `x` with positions on
    `ps_axis`; keepdims=True so the result broadcasts against `x`."""
    amax = jnp.max(jnp.abs(x), axis=_reduce_axes(x.ndim, ps_axis), keepdims=True)
    return jnp.maximum(amax / INT8_MAX, SCALE_FLOOR).astype(jnp.float32)


def quantize_block(x, ps_axis: int):
    """(int8 codes, f32 scales) for a page block; symmetric absmax."""
    s = page_scales(x, ps_axis)
    q = jnp.clip(jnp.round(x / s), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, s


def dequantize_block(q, s, dtype=jnp.float32):
    return q.astype(dtype) * s.astype(dtype)


def scale_struct(segments):
    """Zero-initialized scale trees matching a paged pool's segment
    leaves (pool layout: page axis 1, positions axis 2)."""

    def leaf(a):
        shape = list(a.shape)
        for ax in _reduce_axes(a.ndim, 2):
            shape[ax] = 1
        return jnp.zeros(tuple(shape), jnp.float32)

    return jax.tree.map(leaf, segments)


def kv_page_nbytes(mcfg, page_size: int, quant: bool) -> int:
    """HBM bytes one KV page costs (including its scales when `quant`),
    computed from shape structs — nothing is allocated.  The capacity
    story in one number: int8 pages cost ~1/4 of f32 pages, so a fixed
    byte budget holds ~4x the slots."""
    from repro.models import api

    segs = jax.eval_shape(
        lambda: api.init_paged_cache(
            mcfg, 1, page_size, jnp.int8 if quant else None, rows=not quant
        )
    )
    total = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(segs))
    if quant:
        scales = jax.eval_shape(lambda: scale_struct(segs))
        total += sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(scales))
    return int(total)


def pages_for_byte_budget(mcfg, budget_bytes: int, page_size: int, quant: bool) -> int:
    """How many allocatable pages (beyond the null page) fit in
    `budget_bytes` of KV HBM — the apples-to-apples pool sizing the
    quant-vs-f32 capacity comparison uses."""
    per = kv_page_nbytes(mcfg, page_size, quant)
    return max(int(budget_bytes) // per - 1, 1)
