"""DecodeState: family-agnostic cache management behind the serving engine.

The engine's scheduling machinery (admission, EDF shedding, slot
rotation, preemption, failover requeue) never touches cache layout — it
talks to a DecodeState, which owns the per-slot model state and knows
how to (a) splice a prefilled request into slot b and (b) advance the
active slots one decode step at a static lane width.  Four states cover
the model zoo:

* `DenseKVState`   — transformer dense `max_batch x max_len` KV
  rectangles ({"segments": [(L, B, C, ...)], "index": (B,)}); the
  compacted gather/scatter sub-batch decode and the legacy full-width
  emulation both live here, bit-identical to the pre-refactor engine.
  `quantized=True` stores the rectangles int8 with per-(layer, slot,
  head) absmax scales (`serving.quant`) — the decode step dequantizes,
  runs the unchanged f32 math, zeroes stale positions, and re-quantizes
  with fresh scales, all inside ONE jitted executable.
* `PagedKVState`   — the block-paged pool (`serving.paged.PagePool`),
  bucketed prefill, and the paged decode: in place through the page
  tables, or gathered for the int8 pool, MLA latents and pools over a
  mesh of more than one device.
* `RecurrentState` — rglru conv+hidden / rwkv6 wkv state
  ({"layers": [(B, ...)], "index": (B,)}).  Recurrent state advances
  IRREVERSIBLY (there is no per-position cache to rewind), so decode is
  ALWAYS the gathered sub-batch form: only the active slots' states are
  touched, padding lanes duplicate a real slot (idempotent writes), and
  slot rotation/compaction work exactly like the transformer path.
* `CrossAttnState` — whisper encoder outputs (cross KV) + decoder self
  KV.  Prefill encodes the request's frame embeddings (padded to a
  fixed `enc_len` so one executable serves every request) and the
  decoder prompt; decode is gathered like `RecurrentState`.

Every state exposes the same surface:

    prefill(fn, params, b, seq, frames=None) -> last-token logits
    decode(fn, params, next_token, active)   -> (logits, lane-map)
    release(b); place(mesh); capacity; paged/pool/buckets/cache

`fn` is the ENGINE's jitted decode/prefill attribute, passed per call —
tests stub `engine._decode`/`engine._prefill` after construction and the
state must honor the stub, not a captured original.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.config import ModelConfig
from . import paged as paged_kv
from . import quant

Params = Any


# -- generic tree helpers (re-exported by engine.py for test access) ----------

def _tree_set_slot(batched, single, b: int):
    """Write `single` (batch dim 1 or absent on index leaves) into slot b
    of `batched` along the batch dimension."""
    def leaf(dst, src):
        if dst.ndim == 0:
            return src if src.ndim == 0 else src.reshape(())
        # find the batch dim: first dim where dst differs from src by
        # factor max_batch vs 1 — conventionally dims named (B,...) or
        # (L,B,...) (stacked segments).
        if dst.ndim == src.ndim:
            for axis in range(dst.ndim):
                if src.shape[axis] == 1 and dst.shape[axis] > 1:
                    idx = [slice(None)] * dst.ndim
                    idx[axis] = slice(b, b + 1)
                    return dst.at[tuple(idx)].set(src.astype(dst.dtype))
        return dst
    return jax.tree.map(leaf, batched, single)


def _gather_slots(cache, sel):
    """Compact the cache slices of slots `sel` into a dense sub-cache.
    Segment leaves are (L, B, C, ...) — batch on axis 1; "index" is (B,)."""
    return {
        "segments": jax.tree.map(lambda a: jnp.take(a, sel, axis=1),
                                 cache["segments"]),
        "index": jnp.take(cache["index"], sel, axis=0),
    }


def _scatter_slots(cache, sub, sel):
    """Write an advanced sub-cache back into slots `sel`.  Padding lanes
    duplicate a real slot with identical content, so repeated indices in
    `sel` write identical values (scatter order is irrelevant)."""
    segs = jax.tree.map(
        lambda full, part: full.at[:, sel].set(part.astype(full.dtype)),
        cache["segments"], sub["segments"])
    idx = cache["index"].at[sel].set(sub["index"])
    return {"segments": segs, "index": idx}


def _gather_layers(cache, sel):
    """Layers-layout gather: every leaf carries the batch on axis 0
    ({"layers": [(B, ...)], "index": (B,)} — rglru/rwkv6/whisper)."""
    return {
        "layers": jax.tree.map(lambda a: jnp.take(a, sel, axis=0),
                               cache["layers"]),
        "index": jnp.take(cache["index"], sel, axis=0),
    }


def _scatter_layers(cache, sub, sel):
    layers = jax.tree.map(
        lambda full, part: full.at[sel].set(part.astype(full.dtype)),
        cache["layers"], sub["layers"])
    idx = cache["index"].at[sel].set(sub["index"])
    return {"layers": layers, "index": idx}


def _rewind_inactive(index, inactive: list[int]):
    """ONE batched scatter-add rewinding every slot that did not advance
    this step (the PR-4 code dispatched a separate `.at[b].add(-1)` per
    inactive slot)."""
    return index.at[jnp.asarray(inactive, jnp.int32)].add(-1)


_GATHER = jax.jit(_gather_slots)
# the state drops the old cache the moment the scatter returns, so the
# full-size buffers are donated — on accelerators the scatter updates in
# place instead of allocating a second (L, max_batch, clen, ...) cache
_SCATTER = jax.jit(_scatter_slots, donate_argnums=(0,))
_GATHER_L = jax.jit(_gather_layers)
_SCATTER_L = jax.jit(_scatter_layers, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _decode_fn(mcfg: ModelConfig):
    """Shared per-config jitted decode (engines with the same config —
    e.g. benchmark variants — reuse one trace cache).  Bounded: a config
    sweep evicts old executables instead of retaining them forever."""
    def decode(p, t, c):
        return api.decode_step(mcfg, p, t, c)

    return jax.jit(decode)


@functools.lru_cache(maxsize=8)
def _prefill_fn(mcfg: ModelConfig, max_len: int):
    def prefill(p, toks):
        return api.prefill(mcfg, p, {"tokens": toks}, max_len)

    return jax.jit(prefill)


@functools.lru_cache(maxsize=8)
def _whisper_prefill_fn(mcfg: ModelConfig, max_len: int):
    """Whisper prefill takes (params, frames, tokens): encode the frame
    embeddings, run the decoder prompt, fill self+cross caches."""
    return jax.jit(
        lambda p, frames, toks: api.prefill(
            mcfg, p, {"embeds": frames, "tokens": toks}, max_len))


def _lane_map(sel: list[int]) -> dict[int, int]:
    """slot id -> first lane carrying it (padding lanes repeat slots)."""
    lane: dict[int, int] = {}
    for j, b in enumerate(sel):
        lane.setdefault(b, j)
    return lane


def state_for(mcfg: ModelConfig, family: str | None = None) -> type:
    """The DecodeState class serving `mcfg`'s family (dense layouts)."""
    fam = family or mcfg.family
    if fam == "transformer":
        return DenseKVState
    if fam == "whisper":
        return CrossAttnState
    return RecurrentState


# -- dense transformer rectangles ---------------------------------------------

@functools.lru_cache(maxsize=8)
def _dense_quant_step_fn(mcfg: ModelConfig):
    """One jitted executable for the int8 dense decode step: gather the
    selected slots' codes+scales, dequantize, run the unchanged f32
    `decode_step`, zero positions past each slot's new length (stale
    garbage would inflate the absmax), re-quantize with fresh scales,
    scatter back.  Codes/scales are donated — the update is in place."""
    def run(params, toks, codes, scales, index, sel):
        sub_codes = jax.tree.map(lambda a: jnp.take(a, sel, axis=1), codes)
        sub_scales = jax.tree.map(lambda a: jnp.take(a, sel, axis=1), scales)
        sub_idx = jnp.take(index, sel, axis=0)
        segs = jax.tree.map(
            lambda q, s: quant.dequantize_block(q, s, mcfg.jdtype),
            sub_codes, sub_scales)
        logits, new = api.decode_step(mcfg, params, toks,
                                      {"segments": segs, "index": sub_idx})

        def mask_stale(leaf):
            # live positions after this step: j <= old index (the step
            # wrote slot `old index`); leaf axes are (L, w, C, ...)
            live = jnp.arange(leaf.shape[2])[None, :] <= sub_idx[:, None]
            m = jnp.expand_dims(live, axis=(0,) + tuple(range(3, leaf.ndim)))
            return jnp.where(m, leaf, 0.0)

        masked = jax.tree.map(mask_stale, new["segments"])
        new_codes = jax.tree.map(lambda x: quant.quantize_block(x, 2)[0],
                                 masked)
        new_scales = jax.tree.map(lambda x: quant.page_scales(x, 2), masked)
        codes = jax.tree.map(lambda full, part: full.at[:, sel].set(part),
                             codes, new_codes)
        scales = jax.tree.map(lambda full, part: full.at[:, sel].set(part),
                              scales, new_scales)
        return logits, codes, scales, index.at[sel].set(new["index"])
    return jax.jit(run, donate_argnums=(2, 3))


def _quant_scale_shape(a) -> tuple:
    shape = list(a.shape)
    for ax in (2, a.ndim - 1):
        shape[ax] = 1
    return tuple(shape)


class DenseKVState:
    """Transformer dense KV rectangles; optional int8 storage."""

    kind = "dense"
    paged = False
    pool = None
    buckets: tuple = ()

    def __init__(self, mcfg: ModelConfig, max_batch: int, max_len: int, *,
                 decode_batch: int, compact: bool, quantized: bool = False,
                 rewind_hook=None):
        self.mcfg = mcfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.decode_batch = decode_batch
        self.compact = compact
        self.capacity = max_len
        self.quantized = quantized
        # late-bound so tests can monkeypatch engine._rewind_inactive
        self._rewind = rewind_hook or _rewind_inactive
        base = api.init_cache(mcfg, max_batch, max_len)
        if quantized:
            self.cache = {
                "segments": jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.int8), base["segments"]),
                "index": jnp.zeros((max_batch,), jnp.int32)}
            self.scales = jax.tree.map(
                lambda a: jnp.zeros(_quant_scale_shape(a), jnp.float32),
                base["segments"])
        else:
            self.cache = base
            # per-slot cache lengths (vector index -> mixed-length batching)
            self.cache["index"] = jnp.zeros((max_batch,), jnp.int32)
            self.scales = None

    def place(self, mesh) -> None:
        if self.quantized:
            return      # int8 rectangles stay on the default placement
        from repro.parallel.sharding import cache_shardings
        self.cache = jax.device_put(
            self.cache, cache_shardings(mesh, self.cache, self.mcfg.kv_heads,
                                        self.max_batch))

    def prefill(self, fn, params, b: int, seq: np.ndarray, frames=None):
        toks = jnp.asarray(seq[None, :], jnp.int32)
        last, cache1 = fn(params, toks)
        if self.quantized:
            codes1 = jax.tree.map(lambda x: quant.quantize_block(x, 2)[0],
                                  cache1["segments"])
            scales1 = jax.tree.map(lambda x: quant.page_scales(x, 2),
                                   cache1["segments"])
            segs = _tree_set_slot(self.cache["segments"], codes1, b)
            self.scales = _tree_set_slot(self.scales, scales1, b)
            self.cache = {"segments": segs,
                          "index": self.cache["index"].at[b].set(len(seq))}
        else:
            idx_vec = self.cache["index"]
            self.cache = _tree_set_slot(self.cache, cache1, b)
            self.cache["index"] = idx_vec.at[b].set(len(seq))
        return last

    def decode(self, fn, params, next_token: np.ndarray, active: list[int]):
        if self.quantized:
            # always gathered: only active slots dequantize/requantize,
            # so the full-width rewind never runs over int8 codes
            sel = active + [active[0]] * (self.decode_batch - len(active))
            sel_arr = jnp.asarray(sel, jnp.int32)
            qfn = _dense_quant_step_fn(self.mcfg)
            logits, segs, scales, idx = qfn(
                params, jnp.asarray(next_token[sel]),
                self.cache["segments"], self.scales,
                self.cache["index"], sel_arr)
            self.cache = {"segments": segs, "index": idx}
            self.scales = scales
            return logits, _lane_map(sel)
        if self.compact and self.decode_batch < self.max_batch:
            # compacted sub-batch decode: gather the active slots' cache
            # slices, decode at static width decode_batch, scatter back.
            # Padding lanes (fewer active than decode_batch) repeat the
            # first active slot — identical inputs give identical lane
            # results, so the duplicate scatter writes are idempotent.
            sel = active + [active[0]] * (self.decode_batch - len(active))
            sel_arr = jnp.asarray(sel, jnp.int32)
            sub = _GATHER(self.cache, sel_arr)
            logits, new_sub = fn(params, jnp.asarray(next_token[sel]), sub)
            self.cache = _SCATTER(self.cache, new_sub, sel_arr)
            return logits, _lane_map(sel)
        logits, new_cache = fn(params, jnp.asarray(next_token), self.cache)
        self.cache = new_cache
        # full-width decode advanced every slot; slots not advancing
        # this step must not advance their cache index (one batched
        # scatter-add, not a per-slot dispatch loop)
        inactive = [b for b in range(self.max_batch) if b not in active]
        if inactive:
            self.cache["index"] = self._rewind(self.cache["index"], inactive)
        return logits, {b: b for b in active}

    def release(self, b: int) -> None:
        pass


# -- block-paged transformer pool ---------------------------------------------

class PagedKVState:
    """Block-paged KV: PagePool + bucketed prefill + paged decode."""

    kind = "paged"
    paged = True
    cache = None

    def __init__(self, mcfg: ModelConfig, max_batch: int, max_len: int, *,
                 decode_batch: int, compact: bool, page_size: int,
                 num_pages: int | None, bucket_min: int,
                 quantized: bool = False, mesh=None):
        self.mcfg = mcfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.decode_batch = decode_batch
        self.compact = compact
        self.quantized = quantized
        # the one choice of decode path: a bf16/f32 K/V pool on one device
        # is read in place by the paged kernel, from the row layout it
        # reads; the int8 pool, MLA latents and a pool over a mesh of more
        # than one device (`mesh`, the one `place` will get: the kernel
        # reads one device's pool) keep heads on their own axis and gather
        self.in_place = not quantized and not mcfg.use_mla \
            and (mesh is None or mesh.size == 1)
        self.pool = paged_kv.PagePool(
            mcfg, max_batch, max_len, page_size=page_size,
            num_pages=num_pages, quant=quantized, rows=self.in_place)
        self.buckets = paged_kv.prefill_buckets(max_len, bucket_min)
        self.capacity = paged_kv.pool_token_capacity(self.pool, max_len)

    def place(self, mesh) -> None:
        from repro.parallel.sharding import paged_cache_shardings
        self.pool.segments = jax.device_put(
            self.pool.segments,
            paged_cache_shardings(mesh, self.pool.segments,
                                  self.mcfg.kv_heads))
        if self.quantized:
            # scale leaves keep kvh on axis 3 (keepdims layout),
            # so the same placement rule applies
            self.pool.scales = jax.device_put(
                self.pool.scales,
                paged_cache_shardings(mesh, self.pool.scales,
                                      self.mcfg.kv_heads))

    def decode_fn(self):
        """The jitted decode this state's pool takes (`fn` of `decode`)."""
        if self.in_place or self.quantized:
            return paged_kv.paged_decode_fn(self.mcfg, self.quantized)
        return paged_kv.gathered_decode_fn(self.mcfg)

    def prefill(self, fn, params, b: int, seq: np.ndarray, frames=None):
        """Bucket-padded prefill of `seq` into slot b's pages; returns
        the (1, 1, V) last-real-token logits."""
        plen = len(seq)
        bucket = paged_kv.bucket_for(plen, self.buckets)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = seq
        pfn = paged_kv.paged_prefill_fn(self.mcfg, bucket,
                                        self.pool.page_size, self.quantized)
        trow = self.pool.table_row(b, bucket // self.pool.page_size)
        if self.quantized:
            last, self.pool.segments, self.pool.scales = pfn(
                params, toks, plen, self.pool.segments,
                self.pool.scales, trow)
        else:
            last, self.pool.segments = pfn(
                params, toks, plen, self.pool.segments, trow)
        self.pool.index[b] = plen
        return last

    def _width(self) -> int:
        return self.decode_batch if self.compact else self.max_batch

    def pages_read(self, active: list[int]) -> int:
        """Pool pages one decode of `active` reads: each slot's pages up
        to its length when the decode reads in place (the kernel may
        also fetch the null tail of a slot's last block), every lane's
        whole table row when it gathers."""
        if not self.in_place:
            return self._width() * self.pool.pages_per_slot
        ps = self.pool.page_size
        return int(sum(-(-int(self.pool.index[b]) // ps) for b in active))

    def decode(self, fn, params, next_token: np.ndarray, active: list[int]):
        """One paged decode at a fixed lane width (decode_batch when
        compacting, max_batch for the full-width emulation) — a single
        executable either way.  Padding lanes get the null table row and
        length 0: they read nothing and write into the null page."""
        width = self._width()
        n = len(active)
        act = np.asarray(active)
        tables_sel = np.zeros((width, self.pool.pages_per_slot), np.int32)
        tables_sel[:n] = self.pool.tables[act]
        index_sel = np.zeros((width,), np.int32)
        index_sel[:n] = self.pool.index[act]
        toks = np.zeros((width, 1), np.int32)
        toks[:n] = next_token[act]
        self.pool.stats["kv_pages_read"] += self.pages_read(active)
        self.pool.stats["kv_pages_capacity"] += width * self.pool.pages_per_slot
        if self.quantized:
            logits, self.pool.segments, self.pool.scales = fn(
                params, jnp.asarray(toks), self.pool.segments,
                self.pool.scales, tables_sel, index_sel)
        else:
            logits, self.pool.segments = fn(
                params, jnp.asarray(toks), self.pool.segments, tables_sel,
                index_sel)
        # page-table bookkeeping is host-side numpy: advance the lengths
        # here instead of round-tripping them through the device
        self.pool.index[act] += 1
        return logits, _lane_map(active)

    def release(self, b: int) -> None:
        self.pool.release(b)


# -- recurrent (rglru / rwkv6) and encoder-decoder (whisper) ------------------

class _LayersState:
    """Shared machinery for {"layers": [(B, ...)], "index": (B,)} caches:
    per-slot vector-indexed gather/scatter with the batch on axis 0.

    Decode is ALWAYS the gathered sub-batch form at static width
    `decode_batch`: recurrent state advances irreversibly, so inactive
    slots must never be run through the model (the transformer
    full-width emulation rewinds a position index; a wkv/conv state has
    nothing to rewind).  Padding lanes duplicate a real slot; the
    duplicate scatter writes are identical, hence idempotent."""

    paged = False
    pool = None
    buckets: tuple = ()

    def __init__(self, mcfg: ModelConfig, max_batch: int, max_len: int, *,
                 decode_batch: int, enc_len: int | None = None):
        self.mcfg = mcfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.decode_batch = decode_batch
        self.compact = True          # gathered decode is structural here
        self.capacity = max_len
        self.enc_len = enc_len or max_len
        self.cache = api.init_cache(mcfg, max_batch, max_len,
                                    enc_len=self.enc_len)
        self.cache["index"] = jnp.zeros((max_batch,), jnp.int32)

    def place(self, mesh) -> None:
        # params shard over the mesh; recurrent/cross-attn state leaves
        # are small (B, ...) tensors and stay on the default placement
        pass

    def _splice(self, b: int, cache1, plen: int) -> None:
        idx_vec = self.cache["index"]
        self.cache = _tree_set_slot(self.cache, cache1, b)
        self.cache["index"] = idx_vec.at[b].set(plen)

    def decode(self, fn, params, next_token: np.ndarray, active: list[int]):
        sel = active + [active[0]] * (self.decode_batch - len(active))
        sel_arr = jnp.asarray(sel, jnp.int32)
        sub = _GATHER_L(self.cache, sel_arr)
        logits, new_sub = fn(params, jnp.asarray(next_token[sel]), sub)
        self.cache = _SCATTER_L(self.cache, new_sub, sel_arr)
        return logits, _lane_map(sel)

    def release(self, b: int) -> None:
        pass


class RecurrentState(_LayersState):
    """rglru conv+hidden / rwkv6 wkv state (plus rglru's ring KV on its
    interleaved attention layers)."""

    kind = "recurrent"

    def prefill(self, fn, params, b: int, seq: np.ndarray, frames=None):
        toks = jnp.asarray(seq[None, :], jnp.int32)
        last, cache1 = fn(params, toks)
        self._splice(b, cache1, len(seq))
        return last


class CrossAttnState(_LayersState):
    """Whisper: encoder outputs (cross KV) + decoder self KV.  Request
    frame embeddings are padded/truncated to the fixed `enc_len` window
    so every prefill of a given prompt length shares one executable;
    requests without frames encode a zero (silence) window."""

    kind = "cross_attn"

    def _fixed_frames(self, frames) -> jnp.ndarray:
        d = self.mcfg.d_model
        out = np.zeros((1, self.enc_len, d), np.float32)
        if frames is not None:
            f = np.asarray(frames, np.float32)
            if f.ndim == 3:
                f = f[0]
            take = min(f.shape[0], self.enc_len)
            out[0, :take] = f[:take]
        return jnp.asarray(out)

    def prefill(self, fn, params, b: int, seq: np.ndarray, frames=None):
        toks = jnp.asarray(seq[None, :], jnp.int32)
        last, cache1 = fn(params, self._fixed_frames(frames), toks)
        self._splice(b, cache1, len(seq))
        return last
