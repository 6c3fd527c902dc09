"""Block-paged KV cache + bucketed prefill for the serving engine.

The dense engine reserves a `(max_batch, max_len)` KV rectangle per slot
and compiles a fresh prefill executable for every distinct prompt
length.  This module replaces both:

* **Pages** — KV lives in per-layer pools of fixed-size pages
  (`models.api.init_paged_cache`); each slot owns a list of physical
  pages recorded in a per-slot page table, so HBM holds live tokens, not
  rectangles.  In the pool the in-place decode reads, a K or V page of
  one layer is one contiguous (ps, W) slab: a position's kv heads side by
  side in a lane-aligned row.  Page 0 is a
  reserved null page: every unused table entry points at it and its
  contents are never read (attention masks by per-slot length).
  Allocation/free is host-side free-list accounting (`PagePool`), cheap
  and exact.
* **Bucketed prefill** — prompts are right-padded to the next
  power-of-two bucket, so an arbitrary prompt mix compiles at most
  `len(prefill_buckets(...))` prefill executables.  Causal attention
  makes the padding exact: positions `< plen` never attend to the pad
  tail.  The prefill scatter is RAGGED (per-page): pad positions are
  zeroed and table entries whose page starts at or past `plen` are
  redirected to the null page inside the trace, so bucket padding never
  occupies — or pollutes — pages past the true prompt length; a page's
  only nonzero contents are real KV.
* **Decode in place** — `paged_decode_fn` runs
  `transformer.paged_decode_step`, whose attention kernel reads each
  lane's live pages straight from the pool through its table row and
  folds in the new token's own K/V; after the layer loop one scatter
  writes the new token's K/V of every layer into its page.  No dense
  sub-cache is built, no page is copied, and a lane reads only the pages
  it holds (padding lanes hold none).
* **Gathered decode** — the int8 pool, MLA latent pools and pools over a
  mesh of more than one device keep the gather path: their K/V keep the
  kv heads on their own axis (the axis a mesh shards), and the decode
  gathers the selected slots' pages into the dense `(n, C, ...)` layout
  `transformer.decode_step` understands, runs it, and scatters the
  advanced pages back (`kv_gather`/`kv_scatter`).
* **Int8 quantization** — with `quant=True` (`MOZART_KV_QUANT=1`) pages
  are stored int8, heads on their own axis, with per-(layer, page,
  kv-head) float32 scales (`serving.quant`): gather dequantizes into the
  f32 dense sub-cache, scatter re-quantizes with fresh scales, and
  positions at or past each slot's length are zeroed before
  re-quantization so stale garbage in reused pages can never inflate a
  scale and crush the live tokens' resolution.  ~4x the slots per HBM
  byte (`quant.pages_for_byte_budget`).

Page tables and per-slot lengths live as host `numpy` arrays and enter
the jitted functions as plain array arguments: every step passes the
same shapes, so steady-state serving dispatches zero fresh compiles no
matter how tables churn.  The functions that make the jitted programs
are module-level and `lru_cache`'d per config, so engines sharing a
config reuse one trace cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import to_pool_rows
from repro.models import api, transformer
from repro.models.config import ModelConfig

from . import quant as kvq


def prefill_buckets(max_len: int, min_bucket: int = 16) -> tuple[int, ...]:
    """Power-of-two prompt-length buckets: `min_bucket, 2*min_bucket, ...`
    up to the first bucket that covers `max_len - 1` (prompts of
    `max_len` or longer are rejected at admission — decode needs at
    least one free position)."""
    b = 1 << max(0, int(min_bucket) - 1).bit_length()
    if b < 1:
        b = 1
    out = [b]
    while out[-1] < max_len - 1:
        out.append(out[-1] * 2)
    return tuple(out)


def bucket_for(plen: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket that holds a `plen`-token prompt."""
    for b in buckets:
        if plen <= b:
            return b
    raise ValueError(f"prompt of {plen} tokens exceeds the largest bucket {buckets[-1]}")


class PagePool:
    """Fixed-size KV pages with per-slot page tables and host-side
    free-list accounting.  Not thread-safe: the serving engine is the
    single writer."""

    def __init__(
        self,
        mcfg: ModelConfig,
        max_batch: int,
        max_len: int,
        *,
        page_size: int = 16,
        num_pages: int | None = None,
        dtype=None,
        quant: bool = False,
        rows: bool = True,
    ):
        if page_size < 1 or page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two, got {page_size}")
        self.page_size = page_size
        self.max_batch = max_batch
        self.pages_per_slot = -(-max_len // page_size)
        # default: capacity parity with the dense cache (+1 null page);
        # pass a smaller num_pages to trade capacity for density — the
        # engine preempts under pressure instead of overflowing
        self.num_pages = num_pages or 1 + max_batch * self.pages_per_slot
        if self.num_pages < 2:
            raise ValueError("need at least one allocatable page beyond the null page")
        # quant: int8 pages + per-(layer, page, kv-head) f32 scales; the
        # prefill/decode builders below dequantize on gather and
        # re-quantize on scatter (serving.quant).  rows: K/V in the row
        # layout the in-place decode reads; the int8 pool, and a pool the
        # caller shards over a mesh, keep the kv heads on their own axis
        self.quant = quant
        self.segments = api.init_paged_cache(
            mcfg, self.num_pages, page_size, jnp.int8 if quant else dtype,
            rows=rows and not quant,
        )
        self.scales = kvq.scale_struct(self.segments) if quant else None
        # tables/index are HOST state (numpy): they enter jitted code as
        # ordinary array arguments, never as baked-in constants, so page
        # churn can't mint fresh executables
        self.tables = np.zeros((max_batch, self.pages_per_slot), np.int32)
        self.index = np.zeros((max_batch,), np.int32)
        self._free = list(range(self.num_pages - 1, 0, -1))  # pop() allocates ascending
        self._owned: list[list[int]] = [[] for _ in range(max_batch)]
        # kv_pages_read / kv_pages_capacity: the share of the lanes' page
        # capacity the decodes read (PagedKVState.pages_read)
        self.stats = {
            "page_allocs": 0,
            "page_frees": 0,
            "peak_pages_in_use": 0,
            "kv_pages_read": 0,
            "kv_pages_capacity": 0,
        }

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def page_nbytes(self) -> int:
        """HBM bytes one page costs across every layer's pools (plus its
        scale entries when quantized) — the unit `quant.
        pages_for_byte_budget` sizes byte-matched pools with."""
        total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.segments))
        if self.quant:
            total += sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.scales))
        return total // self.num_pages

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def owned(self, b: int) -> tuple[int, ...]:
        return tuple(self._owned[b])

    def ensure(self, b: int, n_tokens: int) -> bool:
        """Grow slot `b` to hold `n_tokens`; False if the free list is
        short (caller preempts or waits).  Never partially allocates."""
        need = self.pages_for(n_tokens)
        have = len(self._owned[b])
        if need <= have:
            return True
        if need - have > len(self._free) or need > self.pages_per_slot:
            return False
        fresh = [self._free.pop() for _ in range(need - have)]
        self._owned[b].extend(fresh)
        self.tables[b, have:need] = fresh
        self.stats["page_allocs"] += len(fresh)
        self.stats["peak_pages_in_use"] = max(self.stats["peak_pages_in_use"], self.pages_in_use)
        return True

    def release(self, b: int) -> None:
        """Return slot `b`'s pages to the free list and null its table."""
        pages = self._owned[b]
        if pages:
            self.stats["page_frees"] += len(pages)
            self._free.extend(reversed(pages))
            self._owned[b] = []
            self.tables[b] = 0
        self.index[b] = 0

    def table_row(self, b: int, n_entries: int) -> np.ndarray:
        """The first `n_entries` table entries of slot `b` (null-padded) —
        the bucket-sized view a padded prefill scatters through."""
        row = (self._owned[b] + [0] * n_entries)[:n_entries]
        return np.asarray(row, np.int32)


def _gather_pages(segments, tables_sel):
    """Pool pages -> the dense (n, C, ...) cache layout, via per-slot
    tables.  tables_sel: (n, pages_per_slot) physical page ids."""
    n, npp = tables_sel.shape

    def leaf(a):  # (L, P, ps, ...)
        g = jnp.take(a, tables_sel, axis=1)  # (L, n, npp, ps, ...)
        return g.reshape(a.shape[0], n, npp * a.shape[2], *a.shape[3:])

    return jax.tree.map(leaf, segments)


def _scatter_pages(segments, dense, tables_sel):
    """Write an advanced dense sub-cache back through the page tables.
    Duplicate physical ids only occur for the never-read null page, so
    scatter order is irrelevant."""
    n, npp = tables_sel.shape

    def leaf(a, d):  # a: (L, P, ps, ...); d: (L, n, C, ...)
        dp = d.reshape(a.shape[0], n, npp, a.shape[2], *a.shape[3:])
        return a.at[:, tables_sel].set(dp.astype(a.dtype))

    return jax.tree.map(leaf, segments, dense)


def _write_token(segments, new_kv, tables_sel, index_sel):
    """Write each lane's new token's K/V of every layer (new_kv: per
    segment {"k", "v"} of (L, n, kvh, hd)) into its page:
    tables_sel[b, index_sel[b] // ps] at offset index_sel[b] % ps.  A
    padding lane (null table row, length 0) writes into the null page."""
    ps = segments[0]["k"].shape[2]
    pages = jnp.take_along_axis(tables_sel, (index_sel // ps)[:, None], axis=1)[:, 0]
    offs = index_sel % ps

    def write(a, kv):  # a: (L, P, ps, W); kv: (L, n, kvh, hd)
        # one (layer, page, offset) index per row keeps every update a
        # contiguous W-wide row of the pool's own layout: the scatter runs
        # in place instead of relaying the whole pool out around it
        layer = jnp.arange(a.shape[0])[:, None]
        rows = to_pool_rows(kv, a.shape[-1]).astype(a.dtype)
        return a.at[layer, pages[None, :], offs[None, :]].set(rows)

    return [
        {name: write(a, kv[name]) for name, a in seg.items()}
        for seg, kv in zip(segments, new_kv)
    ]


def _gather_pages_dequant(segments, scales, tables_sel):
    """Int8 pool pages -> dequantized f32 dense (n, C, ...) cache layout.
    Each page's scale broadcasts over its positions (and head_dim) via
    the keepdims-1 axes `quant.page_scales` left in place."""
    n, npp = tables_sel.shape

    def leaf(a, s):  # a: (L, P, ps, ...) int8; s: (L, P, 1, ...) f32
        g = jnp.take(a, tables_sel, axis=1)  # (L, n, npp, ps, ...)
        gs = jnp.take(s, tables_sel, axis=1)  # (L, n, npp, 1, ...)
        d = kvq.dequantize_block(g, gs)
        return d.reshape(a.shape[0], n, npp * a.shape[2], *a.shape[3:])

    return jax.tree.map(leaf, segments, scales)


def _scatter_pages_quant(segments, scales, dense, tables_sel, new_len):
    """Re-quantize an advanced dense sub-cache back into int8 pages with
    FRESH per-page scales.  Positions at or past each lane's new length
    (`new_len`, (n,)) are zeroed first: a reused page's stale garbage —
    or the never-read null page's — must not inflate a scale and crush
    the resolution of the page's live tokens."""
    n, npp = tables_sel.shape
    seg_leaves, treedef = jax.tree.flatten(segments)
    scale_leaves = jax.tree.leaves(scales)
    dense_leaves = jax.tree.leaves(dense)
    out_segs, out_scales = [], []
    for a, s, d in zip(seg_leaves, scale_leaves, dense_leaves):
        pos = jnp.arange(d.shape[2])
        live = (pos[None, :] < new_len[:, None]).reshape(
            1, n, d.shape[2], *([1] * (d.ndim - 3))
        )
        dp = jnp.where(live, d, 0).reshape(
            a.shape[0], n, npp, a.shape[2], *a.shape[3:]
        )
        q, qs = kvq.quantize_block(dp, ps_axis=3)
        out_segs.append(a.at[:, tables_sel].set(q))
        out_scales.append(s.at[:, tables_sel].set(qs))
    return (
        jax.tree.unflatten(treedef, out_segs),
        jax.tree.unflatten(jax.tree.structure(scales), out_scales),
    )


@functools.lru_cache(maxsize=8)
def paged_decode_fn(mcfg: ModelConfig, quantized: bool = False):
    """The jitted paged decode for a config, one executable per
    selection width; the pool buffers are donated so the writes update
    them in place.  Slot lengths advance on the host (the caller knows
    exactly which slots stepped), so only logits and the pool round-trip
    the device.

    The bf16/f32 program decodes a row-layout K/V pool in place
    (`transformer.paged_decode_step`, then `kv_write` puts the new token's
    K/V into its page).  The int8 program (which takes and returns the
    scale tree alongside the codes) gathers and re-quantizes.  Pools that
    neither reads take `gathered_decode_fn` (`PagedKVState.decode_fn`
    picks)."""

    def paged_decode(params, tokens, segments, tables_sel, index_sel):
        logits, new_kv = transformer.paged_decode_step(
            mcfg, params, tokens, segments, tables_sel, index_sel
        )
        with jax.named_scope("kv_write"):
            segments = _write_token(segments, new_kv, tables_sel, index_sel)
        return logits, segments

    def paged_decode_int8(params, tokens, segments, scales, tables_sel, index_sel):
        with jax.named_scope("kv_gather"):
            dense = _gather_pages_dequant(segments, scales, tables_sel)
        logits, new = api.decode_step(
            mcfg, params, tokens, {"segments": dense, "index": index_sel}
        )
        with jax.named_scope("kv_scatter"):
            segs2, scales2 = _scatter_pages_quant(
                segments, scales, new["segments"], tables_sel, new["index"]
            )
        return logits, segs2, scales2

    if quantized:
        return jax.jit(paged_decode_int8, donate_argnums=(2, 3))
    return jax.jit(paged_decode, donate_argnums=(2,))


@functools.lru_cache(maxsize=8)
def gathered_decode_fn(mcfg: ModelConfig):
    """Jitted gather -> `decode_step` -> scatter over the page pool: the
    decode of an MLA latent pool, and of a K/V pool that keeps its kv
    heads on their own axis to shard them over a mesh (the paged kernel
    reads one device's pool).  Same arguments and donation as
    `paged_decode_fn`'s."""

    def paged_decode(params, tokens, segments, tables_sel, index_sel):
        with jax.named_scope("kv_gather"):
            dense = _gather_pages(segments, tables_sel)
        logits, new = api.decode_step(
            mcfg, params, tokens, {"segments": dense, "index": index_sel}
        )
        with jax.named_scope("kv_scatter"):
            segments = _scatter_pages(segments, new["segments"], tables_sel)
        return logits, segments

    return jax.jit(paged_decode, donate_argnums=(2,))


@functools.lru_cache(maxsize=32)
def paged_prefill_fn(
    mcfg: ModelConfig, bucket: int, page_size: int, quantized: bool = False
):
    """Jitted padded prefill + RAGGED per-page scatter for one bucket
    length.  The prompt arrives right-padded to `bucket`; `plen`
    (traced) selects the real last-token logits, and the prompt's KV
    lands in the pages named by `table_row`.  Pad positions `>= plen`
    are zeroed and table entries whose page starts at or past `plen` are
    redirected to the null page, so the whole-bucket rectangle never
    lands in pages past the true prompt length: a slot's pages hold real
    KV and zeros, nothing else (which is also what keeps the quantized
    variant's per-page absmax scales driven by live tokens only)."""
    if bucket % page_size:
        raise ValueError(f"bucket {bucket} is not a multiple of page_size {page_size}")
    npp_b = bucket // page_size

    def _masked_kv(plen, kvs):
        """Per-segment KV trees with pad positions zeroed, plus the
        null-redirected table-row transform for pages past plen."""
        valid = jnp.arange(bucket) < plen  # (bucket,)
        page_live = (jnp.arange(npp_b) * page_size) < plen  # (npp_b,)
        trees = []
        for seg_kv in kvs:
            if mcfg.use_mla:
                kv_tree = {"latent": seg_kv[0]}
            else:
                kv_tree = {"k": seg_kv[0], "v": seg_kv[1]}
            trees.append(
                jax.tree.map(
                    lambda kv: jnp.where(
                        valid.reshape(1, 1, bucket, *([1] * (kv.ndim - 3))), kv, 0
                    ),
                    kv_tree,
                )
            )
        return trees, page_live

    def _pages(kv):  # (L, 1, bucket, ...) -> (L, npp_b, page_size, ...)
        return kv[:, 0].reshape(kv.shape[0], npp_b, page_size, *kv.shape[3:])

    def paged_prefill(params, toks, plen, segments, table_row):
        logits, _, kvs = transformer.forward(mcfg, params, toks, collect_kv=True)
        last = jax.lax.dynamic_slice_in_dim(logits, plen - 1, 1, axis=1)
        kv_trees, page_live = _masked_kv(plen, kvs)
        row = jnp.where(page_live, table_row, 0)

        def write(a, kv):
            pages = _pages(kv)
            if pages.ndim > a.ndim:  # heads folded into the pool's rows
                pages = to_pool_rows(pages, a.shape[-1])
            return a.at[:, row].set(pages.astype(a.dtype))

        new_segs = [
            jax.tree.map(write, seg_pool, kv_tree)
            for seg_pool, kv_tree in zip(segments, kv_trees)
        ]
        return last, new_segs

    def paged_prefill_int8(params, toks, plen, segments, scales, table_row):
        logits, _, kvs = transformer.forward(mcfg, params, toks, collect_kv=True)
        last = jax.lax.dynamic_slice_in_dim(logits, plen - 1, 1, axis=1)
        kv_trees, page_live = _masked_kv(plen, kvs)
        row = jnp.where(page_live, table_row, 0)
        new_segs, new_scales = [], []
        for seg_pool, seg_scale, kv_tree in zip(segments, scales, kv_trees):
            seg_leaves, treedef = jax.tree.flatten(seg_pool)
            scale_leaves = jax.tree.leaves(seg_scale)
            kv_leaves = jax.tree.leaves(kv_tree)
            out_a, out_s = [], []
            for a, s, kv in zip(seg_leaves, scale_leaves, kv_leaves):
                q, qs = kvq.quantize_block(_pages(kv), ps_axis=2)
                out_a.append(a.at[:, row].set(q))
                out_s.append(s.at[:, row].set(qs))
            new_segs.append(jax.tree.unflatten(treedef, out_a))
            new_scales.append(
                jax.tree.unflatten(jax.tree.structure(seg_scale), out_s)
            )
        return last, new_segs, new_scales

    if quantized:
        return jax.jit(paged_prefill_int8, donate_argnums=(3, 4))
    return jax.jit(paged_prefill, donate_argnums=(3,))


def paged_supported(mcfg: ModelConfig) -> bool:
    """Paged + bucketed serving is exact only where the page/bucket
    assumptions hold: the transformer cache layout, no sliding-window
    ring (pages map positions, not ring slots), and no MoE (pad tokens
    would consume router capacity and perturb real tokens)."""
    return mcfg.family == "transformer" and not mcfg.window and not mcfg.use_moe


def pool_token_capacity(pool: PagePool, max_len: int) -> int:
    """Hard per-slot token ceiling: the engine finishes a request at this
    boundary instead of overrunning its pages."""
    return min(max_len, pool.pages_per_slot * pool.page_size)
