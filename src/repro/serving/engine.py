"""Serving engine: slot-based continuous batching with the Mozart
operator-level batching policy (Insight 2).

A fixed pool of `max_batch` cache slots decodes in lock-step (static
shapes); finished slots are refilled by prefilling queued requests and
splicing their cache into the slot.  The paper's non-uniform batching
maps here as: decode batch size and prefill parallelism are set from the
Mozart `ExecutionPolicy` (batch-agnostic attention wants small per-op
batch with high TP; batch-sensitive projections want the opposite — the
engine's `decode_batch` honors the policy's compromise).

MODEL STATE.  The engine is family-agnostic: per-slot model state lives
behind a `serving.state.DecodeState`, so the SAME admission / EDF
shedding / rotation / preemption / failover machinery serves every
family in `configs/`:

* transformer — `PagedKVState` (block-paged pool, default) or
  `DenseKVState` (dense rectangles, optionally int8 via
  `MOZART_KV_QUANT=dense`);
* rglru / rwkv6 — `RecurrentState` (conv+hidden / wkv state with
  per-slot vector-indexed gather/scatter; decode is always the gathered
  sub-batch form because recurrent state cannot be rewound);
* whisper — `CrossAttnState` (encoder outputs + decoder self KV; the
  request's `frames` embeddings are encoded at admission).

KV STORAGE.  By default (`MOZART_PAGED_KV=1`, transformer family without
SWA/MoE) the KV cache is BLOCK-PAGED: fixed-size pages from a shared
pool, owned per-slot through page tables (`serving.paged.PagePool`),
allocated on admission/growth and freed on finish — HBM holds live
tokens, not `max_batch x max_len` rectangles.  Prefill pads prompts to
power-of-two BUCKETS so an arbitrary prompt-length mix compiles at most
`len(engine.buckets)` prefill executables plus one decode executable.
Decode reads each active slot's live pages in place through its page
table (`transformer.paged_decode_step`) and writes only the new token's
K/V back, token-exact against the dense cache; the int8 pool, MLA
latents and pools over a mesh of more than one device gather pages into
the dense layout `decode_step` expects and scatter back.  When the free
list runs dry the engine preempts the youngest-admitted slot (requeued
at the queue front and later resumed by re-prefilling its tokens).  `paged=False` (or
`MOZART_PAGED_KV=0`) restores the dense rectangles.  `MOZART_KV_QUANT`
stores KV int8 with per-head scales (`serving.quant`): any truthy value
quantizes the paged pool (gather dequantizes, scatter re-quantizes, the
same HBM holds ~4x the slots at token-level — not bit-level — parity);
the value `dense` additionally covers non-paged transformer engines
(per-(layer, slot, head) scales over the dense rectangles).

When `decode_batch < max_batch` the engine runs a COMPACTED sub-batch
decode: the active slots' cache slices are gathered into a dense
(decode_batch, ...) sub-cache, one static-shaped decode runs over that
width, and the advanced slices are scattered back — so the policy's
batch split saves real per-step FLOPs, not just schedule steps.  Slots
rotate in slot-id order (the cursor is keyed to slot ids, not positions,
so admission/finish churn cannot starve or double-serve a slot).  Set
`compact=False` (or `MOZART_COMPACT_DECODE=0`) for the legacy full-width
round-robin emulation, kept for benchmarking against the PR-4 behavior
(transformer only — recurrent/cross-attn states are always gathered).

A `mesh` with a >1 "model" axis makes the policy's TP degree real:
params and KV cache (dense slabs or page pools) are placed with
`parallel.sharding`'s rules and the jitted prefill/decode run sharded
over the mesh.  `mesh=None` is the single-device no-op path.

Requests carry wall-clock marks (`t_submit`/`t_admit`/`t_first`/
`t_done`) from which TTFT/TPOT percentiles are computed, and a
`finish_reason` ("eos", "max_new_tokens", "length" at the cache
boundary, "rejected" for prompts that cannot fit, "capacity" when a lone
request exhausts the page pool, "shed" for deadline/overload shedding,
"poison" when a request exhausts its cluster retry budget).

SLO RESILIENCE.  Requests may carry a `deadline_s` (seconds from
submission).  Admission is deadline-aware: the queue drains
earliest-deadline-first (resumed requests keep their front priority so
preemption/failover recovery stays token-exact; FIFO among requests
without deadlines), and a request whose deadline has already passed —
or whose remaining budget cannot fit its remaining tokens at the
engine's measured per-step pace — is SHED at admission
(`finish_reason="shed"`) instead of wasting decode lanes on tokens
nobody can use (`MOZART_DEADLINE_SHED=0` disables the feasibility
check).  `queue_bound` (`MOZART_QUEUE_BOUND`) bounds the queue: a full
queue sheds new submissions instead of growing without bound —
backpressure the cluster router reads to route around hot replicas.

SAMPLING.  One jitted program (`sampling.sample_tokens`, profiled as
`jit_sample_tokens`) samples every active lane of a decode step from
the logits on the device, advances the engine's device-resident PRNG
key once per active lane in the order the step serves them, and
reduces the whole logits array to an all-finite flag; the host reads
the tokens and the flag together, once a step.  Admission samples each first token with the
same program at width 1.  With the NaN guard on (`MOZART_WATCHDOG_NAN`)
non-finite logits set `health["nan_detected"]`, the step emits nothing
and the key is not advanced, so corrupted KV can never leak garbage
tokens — the cluster watchdog quarantines the replica and the requeue
path recovers its requests token-exactly.

SPANS AND COUNTERS.  With `serving.spans` on, `step()` writes
`serve.step` and, nested inside it, `serve.admit`, one `serve.prefill`
per admitted request, `serve.grow`, `serve.decode` and `serve.sample`
(the sampling program and its one read) into the profiler's trace.
`stats["host_syncs"]` counts every read of a device value by the host:
one per admitted request and one per decode step, guard or no guard
(spec-decode reads its guard and its two token arrays);
`stats["live_slot_steps"]` sums the live slots over decode steps, so
mean occupancy is `live_slot_steps / (decode_steps * max_batch)`.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import numpy as np

from repro.launch import knobs
from repro.models.config import ModelConfig
from . import paged as paged_kv
from . import spans
from . import state as state_mod
from .sampling import sample_tokens

# re-exports: tests and downstream modules address these through the
# engine module (and monkeypatch _rewind_inactive by this name)
from .state import (_GATHER, _SCATTER, _decode_fn, _gather_slots,  # noqa: F401
                    _prefill_fn, _rewind_inactive, _scatter_slots,
                    _tree_set_slot)

Params = Any


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    # SLO deadline in seconds from t_submit; None = no deadline.  The
    # engine sheds the request at admission when it cannot be met.
    deadline_s: float | None = None
    # whisper: precomputed encoder frame embeddings (F, d_model); other
    # families ignore it.  None encodes a zero (silence) window.
    frames: np.ndarray | None = None
    # cluster routing tag: only replicas whose engine serves this model
    # name may run the request (None = any replica)
    model: str | None = None
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str | None = None
    # wall-clock marks for TTFT/TPOT accounting (monotonic seconds)
    t_submit: float | None = None
    t_admit: float | None = None  # first prefill's start; kept on resume
    t_first: float | None = None
    t_done: float | None = None
    admit_seq: int = -1           # engine admission order (preemption picks max)
    requeues: int = 0             # failovers survived (cluster retry budget)


def sample(logits, key, *, lanes, temperature, n, out: dict):
    """Draw the tokens of `lanes` in one program (`sampling.sample_tokens`)
    and return them on the device; the all-finite guard and the advanced
    key go into `out`.  Every token the engine emits is drawn here, so a
    fault injected under this name reaches every request's stream."""
    tokens, out["finite"], out["key"] = sample_tokens(
        logits, lanes, temperature, n, key)
    return tokens


def _rewind_hook(index, inactive):
    """Late-bound module-global lookup so tests monkeypatching
    `engine._rewind_inactive` observe the dense full-width rewind."""
    return _rewind_inactive(index, inactive)


def _kv_quant_mode(kv_quant, paged: bool, mcfg: ModelConfig) -> str:
    """Resolve the engine's KV-quant mode: "paged" (int8 page pool),
    "dense" (int8 dense rectangles), or "" (off).  Any truthy value
    quantizes a paged engine; the explicit value `dense` additionally
    covers dense transformer engines (rings excluded: the stale-position
    zeroing assumes slot j holds position j)."""
    raw = knobs.get_str("MOZART_KV_QUANT") if kv_quant is None else kv_quant
    mode = str(raw).strip().lower()
    if mode in ("0", "", "false", "no", "off"):
        return ""
    if paged:
        return "paged"
    if mode == "dense" and mcfg.family == "transformer" and not mcfg.window:
        return "dense"
    return ""


class ServingEngine:
    def __init__(self, mcfg: ModelConfig, params: Params, *,
                 max_batch: int = 4, max_len: int = 512,
                 decode_batch: int | None = None, eos_id: int = -1,
                 compact: bool | None = None, mesh=None,
                 paged: bool | None = None, page_size: int | None = None,
                 num_pages: int | None = None,
                 kv_quant: bool | str | None = None,
                 enc_len: int | None = None,
                 queue_bound: int | None = None,
                 guard_nan: bool | None = None,
                 shed_deadlines: bool | None = None):
        self.mcfg = mcfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # Mozart Insight 2: batch-agnostic stages (attention) may want a
        # smaller lock-step decode batch than the slot count; when
        # decode_batch < max_batch only that many active slots advance
        # per step, in slot-id rotation, over a compacted sub-cache.
        self.decode_batch = decode_batch or max_batch
        if compact is None:
            compact = knobs.get_bool("MOZART_COMPACT_DECODE")
        # transformer engines honor the knob; recurrent/cross-attn state
        # cannot be rewound, so their decode is ALWAYS the gathered
        # sub-batch form (see serving.state._LayersState)
        self.compact = compact if mcfg.family == "transformer" else True
        if paged is None:
            paged = knobs.get_bool("MOZART_PAGED_KV")
        # paged + bucketed serving is exact only for the plain transformer
        # cache (no SWA ring, no MoE capacity router) — see paged_supported
        self.paged = paged and paged_kv.paged_supported(mcfg)
        quant_mode = _kv_quant_mode(kv_quant, self.paged, mcfg)
        self.kv_quant = quant_mode == "paged"
        self.kv_quant_dense = quant_mode == "dense"
        self._next_slot = 0           # rotation cursor: a SLOT ID
        self.eos_id = eos_id
        self._admit_counter = 0
        # KV headroom one decode step needs; spec-decode engines write
        # k+1 positions per iteration and raise this accordingly
        self._headroom = 1
        # -- resilience knobs: bounded queue, deadline shedding, NaN guard --
        self.queue_bound = queue_bound if queue_bound is not None \
            else knobs.get_int("MOZART_QUEUE_BOUND")
        self.guard_nan = guard_nan if guard_nan is not None \
            else knobs.get_bool("MOZART_WATCHDOG_NAN")
        self.shed_deadlines = shed_deadlines if shed_deadlines is not None \
            else knobs.get_bool("MOZART_DEADLINE_SHED")
        # a sick engine raises flags here instead of raising exceptions;
        # the cluster watchdog reads them and quarantines the replica
        self.health = {"nan_detected": False}
        # EWMA of step wall time: the deadline-feasibility estimate
        self._est_step_s = 0.0
        if self.paged:
            ps = page_size or knobs.get_int("MOZART_KV_PAGE_SIZE")
            self.state = state_mod.PagedKVState(
                mcfg, max_batch, max_len, decode_batch=self.decode_batch,
                compact=self.compact, page_size=ps, num_pages=num_pages,
                bucket_min=knobs.get_int("MOZART_PREFILL_BUCKET_MIN"),
                quantized=self.kv_quant, mesh=mesh)
        elif mcfg.family == "whisper":
            self.state = state_mod.CrossAttnState(
                mcfg, max_batch, max_len, decode_batch=self.decode_batch,
                enc_len=enc_len)
        elif mcfg.family == "transformer":
            self.state = state_mod.DenseKVState(
                mcfg, max_batch, max_len, decode_batch=self.decode_batch,
                compact=self.compact, quantized=self.kv_quant_dense,
                rewind_hook=_rewind_hook)
        else:
            self.state = state_mod.RecurrentState(
                mcfg, max_batch, max_len, decode_batch=self.decode_batch)
        self.pool = self.state.pool
        self.buckets = self.state.buckets
        self.capacity = self.state.capacity
        self.mesh = mesh
        if mesh is not None:
            from repro.parallel.sharding import params_shardings
            self.params = jax.device_put(
                params, params_shardings(mesh, params))
            self.state.place(mesh)
        self.slots: list[Request | None] = [None] * max_batch
        self.queue: list[Request] = []
        self.next_token = np.zeros((max_batch, 1), np.int32)
        self.key = jax.random.PRNGKey(0)
        self._decode = _decode_fn(mcfg)
        self._prefill = state_mod._whisper_prefill_fn(mcfg, max_len) \
            if mcfg.family == "whisper" else _prefill_fn(mcfg, max_len)
        self._paged_decode = self.state.decode_fn() if self.paged else None
        self.stats = {"decode_steps": 0, "prefills": 0,
                      "tokens_out": 0, "live_slot_steps": 0,
                      "preemptions": 0, "rejected": 0,
                      "shed": 0, "nan_steps": 0, "host_syncs": 0}

    @property
    def cache(self):
        """The live model-state pytree (None for paged engines) — owned
        by the DecodeState; exposed for chaos injection and tests."""
        return self.state.cache

    # -- request lifecycle --------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request; returns False when the bounded queue sheds it
        (`finish_reason="shed"`) instead — backpressure, not growth."""
        if req.t_submit is None:
            req.t_submit = time.monotonic()
        if self.queue_bound > 0 and len(self.queue) >= self.queue_bound:
            self._shed(req)
            return False
        self.queue.append(req)
        return True

    @property
    def queue_full(self) -> bool:
        return self.queue_bound > 0 and len(self.queue) >= self.queue_bound

    def _shed(self, req: Request) -> None:
        req.done = True
        req.finish_reason = "shed"
        req.t_done = time.monotonic()
        self.stats["shed"] += 1

    def _slot_pos(self, b: int) -> int:
        """Cache length of slot b = prompt + decoded-in KV.  The newest
        sampled token is in out_tokens but its KV has not been written
        yet (that happens on its decode step), hence the -1."""
        req = self.slots[b]
        return len(req.prompt) + len(req.out_tokens) - 1

    def _finish(self, b: int, reason: str) -> None:
        req = self.slots[b]
        req.done = True
        if req.finish_reason is None:
            req.finish_reason = reason
        req.t_done = time.monotonic()
        self.slots[b] = None
        self.state.release(b)

    def _preempt(self, b: int) -> None:
        """Evict slot b under page pressure: free its pages and requeue
        it at the front; a later admission re-prefills prompt+output and
        resumes decoding where it stopped."""
        req = self.slots[b]
        self.slots[b] = None
        self.state.release(b)
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1

    def _admission_key(self, j: int) -> tuple:
        """Queue drain order: resumed requests first (their front-of-queue
        priority keeps preemption/failover recovery token-exact), then
        earliest deadline (None sorts last), then submission order — so a
        queue with no deadlines drains exactly like the old FIFO."""
        req = self.queue[j]
        dl = req.deadline_s
        return (0 if req.out_tokens else 1,
                dl if dl is not None else float("inf"), j)

    def _deadline_infeasible(self, req: Request) -> bool:
        """True when `req` can no longer meet its deadline: it already
        expired, or the remaining budget cannot fit the remaining tokens
        at the engine's measured per-step pace (EWMA; until a first
        measurement exists only hard-expired requests are shed)."""
        if not self.shed_deadlines or req.deadline_s is None:
            return False
        now = time.monotonic()
        remaining = (req.t_submit or now) + req.deadline_s - now
        if remaining <= 0:
            return True
        left = max(req.max_new_tokens - len(req.out_tokens), 0)
        return self._est_step_s > 0.0 and self._est_step_s * left > remaining

    def _next_admission(self) -> int | None:
        """Index of the next queue entry to admit (deadline-aware), or
        None when the queue is empty.  Requests that cannot meet their
        deadline any more are shed here — admission control — instead of
        occupying a slot to produce tokens past their SLO."""
        while self.queue:
            j = min(range(len(self.queue)), key=self._admission_key)
            req = self.queue[j]
            if self._deadline_infeasible(req):
                self.queue.pop(j)
                self._shed(req)
                continue
            return j
        return None

    def _admit(self) -> None:
        """Prefill queued requests into free slots (continuous batching).
        Prompts that could never decode a single token inside the cache
        are rejected up front instead of silently overrunning the slot."""
        admitted = 0
        with spans.span("admit", queued=len(self.queue)) as sp:
            for b in range(self.max_batch):
                if self.slots[b] is not None or not self.queue:
                    continue
                qi = self._next_admission()
                if qi is None:
                    break
                req = self.queue[qi]
                resumed = bool(req.out_tokens)
                if resumed:
                    # re-prefill everything but the newest token (whose KV
                    # would have been written by its decode step)
                    seq = np.concatenate([
                        np.asarray(req.prompt, np.int32),
                        np.asarray(req.out_tokens[:-1], np.int32)])
                else:
                    seq = np.asarray(req.prompt, np.int32)
                plen = len(seq)
                if plen < 1 or plen + self._headroom > self.capacity:
                    self.queue.pop(qi)
                    req.done = True
                    req.finish_reason = "rejected"
                    req.t_done = time.monotonic()
                    self.stats["rejected"] += 1
                    continue
                # +1: the next decode writes KV at position plen
                if self.paged and not self.pool.ensure(b, plen + 1):
                    break           # pool dry — wait for decode-side frees
                if req.t_admit is None:
                    req.t_admit = time.monotonic()
                with spans.span("prefill", rid=req.rid, tokens=plen,
                                bucket=functools.partial(self._bucket, plen),
                                resumed=resumed):
                    if self.paged:
                        last = self.state.prefill(self._prefill, self.params,
                                                  b, seq)
                    else:
                        last = self._dense_prefill(b, seq, req)
                    self.queue.pop(qi)
                    self.slots[b] = req
                    req.admit_seq = self._admit_counter
                    self._admit_counter += 1
                    self.stats["prefills"] += 1
                    admitted += 1
                    if resumed:
                        self.next_token[b, 0] = req.out_tokens[-1]
                        continue
                    toks, _, self.key = self._sample(
                        last, [0], [req.temperature])
                    tok = int(toks[0])
                    req.out_tokens.append(tok)
                    if req.t_first is None:
                        req.t_first = time.monotonic()
                    self.next_token[b, 0] = tok
                    self.stats["tokens_out"] += 1
                    if len(req.out_tokens) >= req.max_new_tokens or \
                            tok == self.eos_id:
                        # budget spent at admission — never decode past max_new
                        self._finish(b, "eos" if tok == self.eos_id
                                     else "max_new_tokens")
            sp.set_metadata(admitted=admitted)

    def _bucket(self, plen: int) -> int:
        """The length a `plen`-token prefill is padded to."""
        return paged_kv.bucket_for(plen, self.buckets) if self.buckets \
            else plen

    def _dense_prefill(self, b: int, seq: np.ndarray, req: Request):
        """Dense-state prefill hook (SpecDecodeEngine also prefills the
        draft cache here)."""
        return self.state.prefill(self._prefill, self.params, b, seq,
                                  frames=req.frames)

    def _select_active(self, all_active: list[int]) -> list[int]:
        """Pick up to decode_batch slots in slot-id rotation.  The cursor
        is a slot id (not a position into the active list), so slots
        finishing or being admitted between steps cannot re-alias the
        rotation into starving or double-serving a slot."""
        if self.decode_batch >= len(all_active):
            return list(all_active)
        ordered = [b for b in all_active if b >= self._next_slot] + \
                  [b for b in all_active if b < self._next_slot]
        active = ordered[:self.decode_batch]
        self._next_slot = (active[-1] + 1) % self.max_batch
        return active

    # -- decode tick ---------------------------------------------------------
    def step(self) -> int:
        """One lock-step decode over active slots; returns #active."""
        if self.health["nan_detected"]:
            # sick engine: hold all state for the watchdog's quarantine
            # (the requeue path recovers every request token-exactly)
            return 0
        t_step = time.monotonic()
        with spans.span("step") as sp:
            self._admit()
            live = [b for b, r in enumerate(self.slots) if r is not None]
            # cache-boundary: a slot whose next KV write(s) would land at
            # or past capacity finishes NOW instead of overrunning it
            for b in list(live):
                if self._slot_pos(b) + self._headroom > self.capacity:
                    self._finish(b, "length")
                    live.remove(b)
            if self.paged:
                live = self._grow_pages(live)
            sp.set_metadata(live=len(live))
            if not live:
                return 0
            active = self._select_active(live)
            sp.set_metadata(active=len(active))
            if not self._advance(active):
                return 0        # non-finite logits: emitted nothing
        self.stats["decode_steps"] += 1
        self.stats["live_slot_steps"] += len(live)
        dt = time.monotonic() - t_step
        # EWMA per-step pace: the deadline-feasibility estimate _admit
        # sheds against (first measurement seeds it directly)
        self._est_step_s = dt if self._est_step_s == 0.0 \
            else 0.8 * self._est_step_s + 0.2 * dt
        return len(active)

    def _live_positions(self, active: list[int]) -> int:
        """KV positions one decode of `active` reads: each slot's cache
        length plus the position it writes."""
        return sum(self._slot_pos(b) + 1 for b in active)

    def _advance(self, active: list[int]) -> bool:
        """Decode the active slots one step, sample and guard, finish.
        Returns False when the NaN guard swallowed the step.  Subclasses
        (spec-decode) replace this with multi-token propose/verify."""
        fn = self._paged_decode if self.paged else self._decode
        pages = {"pages": functools.partial(self.state.pages_read, active)} \
            if self.paged else {}
        with spans.span("decode", active=len(active),
                        ctx=functools.partial(self._live_positions, active),
                        **pages):
            logits, lane = self.state.decode(fn, self.params,
                                             self.next_token, active)
        with spans.span("sample", n=len(active)):
            toks, finite, key = self._sample(
                logits, [lane[b] for b in active],
                [self.slots[b].temperature for b in active])
        if self.guard_nan and not finite:
            # corrupted KV / sick kernel: emit NOTHING from non-finite
            # logits (garbage tokens would poison the requests' streams
            # beyond token-exact recovery), keep the key as it was, and
            # flag for the watchdog
            self.health["nan_detected"] = True
            self.stats["nan_steps"] += 1
            return False
        self.key = key
        for b, tok in zip(active, toks.tolist()):
            req = self.slots[b]
            req.out_tokens.append(tok)
            self.next_token[b, 0] = tok
            self.stats["tokens_out"] += 1
            if len(req.out_tokens) >= req.max_new_tokens or \
                    tok == self.eos_id:
                self._finish(b, "eos" if tok == self.eos_id
                             else "max_new_tokens")
        return True

    def _sample(self, logits, lanes: list[int], temps: list[float]):
        """Sample a token for each `lanes[i]` row of `logits` at
        temperature `temps[i]` in one program, and read the tokens and the
        all-finite guard in one host read.  Returns `(tokens, finite,
        key)`: the (len(lanes),) tokens, the guard and the advanced key,
        which the caller keeps or drops.  Lane and temperature arrays are
        padded to the logits' width, so the live count never recompiles."""
        w, n = logits.shape[0], len(lanes)
        lane_arr = np.zeros((w,), np.int32)
        lane_arr[:n] = lanes
        temp_arr = np.zeros((w,), np.float32)
        temp_arr[:n] = temps
        drawn: dict = {}
        toks = sample(logits, self.key, lanes=lane_arr, temperature=temp_arr,
                      n=np.int32(n), out=drawn)
        toks, finite = jax.device_get((toks, drawn["finite"]))
        self.stats["host_syncs"] += 1
        return toks[:n], bool(finite), drawn["key"]

    def _grow_pages(self, live: list[int]) -> list[int]:
        """Make every live slot's next KV write backed by a page,
        preempting the youngest-admitted slot under pool pressure; a lone
        slot that exhausts the pool finishes with reason "capacity"."""
        before = self.stats["preemptions"]
        with spans.span("grow") as sp:
            for b in list(live):
                while b in live and \
                        not self.pool.ensure(b, self._slot_pos(b) + 1):
                    victims = [v for v in live if v != b]
                    if not victims:
                        self._finish(b, "capacity")
                        live.remove(b)
                    else:
                        v = max(victims,
                                key=lambda s: self.slots[s].admit_seq)
                        self._preempt(v)
                        live.remove(v)
            sp.set_metadata(preempted=self.stats["preemptions"] - before)
        return live

    def run(self, max_steps: int = 10_000) -> None:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            if self.health["nan_detected"]:
                # a standalone sick engine stops instead of spinning;
                # under a cluster the watchdog quarantines it first
                break
            self.step()
            steps += 1
