"""Speculative decoding (Leviathan et al. [38]; paper §6.2.1 case study).

A small draft model proposes k tokens; the target verifies them in one
batched forward pass.  The draft path is latency-critical while the
verifier is throughput-oriented — exactly the operator-level
latency/throughput split Mozart exploits (draft -> speed-optimized
chiplets, verifier -> throughput-optimized ones).

Two tiers live here:

* the REFERENCE loops — `spec_decode_greedy` is exactly equivalent to
  target-only greedy decoding (the property the tests assert) and
  `spec_decode_sampled` implements the stochastic acceptance rule; both
  re-run full uncached forwards and exist for correctness cross-checks;
* the LIVE engine — `SpecDecodeEngine` co-locates draft and target in
  ONE `ServingEngine` (the paper's fig11 deployment, measured instead of
  analytical): both models keep per-slot KV caches behind
  `serving.state.DenseKVState`, each iteration runs a single jitted
  k-step draft scan (propose) plus a single jitted target
  `decode_window` pass (verify) over the gathered active slots, and
  greedy outputs are token-exact vs the target-only engine — so all the
  admission / deadline / rotation machinery applies unchanged while each
  decode tick lands up to k tokens per slot.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import knobs
from repro.models import api, transformer
from repro.models.config import ModelConfig
from . import spans
from . import state as state_mod
from .engine import Request, ServingEngine
from .state import _GATHER, _SCATTER, _lane_map

Params = Any
Forward = Callable[[jnp.ndarray], jnp.ndarray]   # tokens (1,S) -> logits


@dataclasses.dataclass
class SpecStats:
    iterations: int = 0
    proposed: int = 0
    accepted: int = 0
    bonus: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.proposed, 1)

    @property
    def tokens_per_iteration(self) -> float:
        return (self.accepted + self.bonus) / max(self.iterations, 1)


def spec_decode_greedy(target_fwd: Forward, draft_fwd: Forward,
                       prompt: np.ndarray, *, k: int = 5,
                       max_new_tokens: int = 32
                       ) -> tuple[np.ndarray, SpecStats]:
    """Greedy speculative decoding; output == greedy decode of target."""
    toks = list(int(t) for t in prompt)
    stats = SpecStats()
    while len(toks) - len(prompt) < max_new_tokens:
        stats.iterations += 1
        # draft proposes k tokens autoregressively (greedy)
        d = list(toks)
        for _ in range(k):
            logits = draft_fwd(jnp.asarray([d], jnp.int32))
            d.append(int(jnp.argmax(logits[0, -1])))
        proposal = d[len(toks):]
        stats.proposed += k
        # target verifies in ONE forward over [toks + proposal]
        logits = target_fwd(jnp.asarray([d], jnp.int32))
        # target's greedy choice at each position that predicts
        # proposal[i] is index len(toks)-1+i
        n_accept = 0
        base = len(toks) - 1
        tgt_choice = np.asarray(jnp.argmax(logits[0], axis=-1))
        for i in range(k):
            if tgt_choice[base + i] == proposal[i]:
                n_accept += 1
            else:
                break
        stats.accepted += n_accept
        toks.extend(proposal[:n_accept])
        # bonus token: target's own prediction at the divergence point
        bonus = int(tgt_choice[base + n_accept])
        toks.append(bonus)
        stats.bonus += 1
        if len(toks) - len(prompt) >= max_new_tokens:
            break
    new = toks[len(prompt):len(prompt) + max_new_tokens]
    return np.asarray(new, np.int32), stats


def spec_decode_sampled(target_fwd: Forward, draft_fwd: Forward,
                        prompt: np.ndarray, key, *, k: int = 5,
                        max_new_tokens: int = 32,
                        temperature: float = 1.0
                        ) -> tuple[np.ndarray, SpecStats]:
    """Stochastic speculative sampling with the p/q acceptance rule —
    distributionally equivalent to sampling from the target alone."""
    toks = list(int(t) for t in prompt)
    stats = SpecStats()

    def probs(fwd, seq):
        lg = fwd(jnp.asarray([seq], jnp.int32))[0].astype(jnp.float32)
        return jax.nn.softmax(lg / temperature, axis=-1)

    while len(toks) - len(prompt) < max_new_tokens:
        stats.iterations += 1
        d = list(toks)
        qs = []
        for _ in range(k):
            q = probs(draft_fwd, d)[-1]
            key, kk = jax.random.split(key)
            t = int(jax.random.categorical(kk, jnp.log(q + 1e-30)))
            qs.append((t, q))
            d.append(t)
        stats.proposed += k
        p_all = probs(target_fwd, d)
        base = len(toks) - 1
        n_accept = 0
        for i, (t, q) in enumerate(qs):
            p = p_all[base + i]
            key, kk = jax.random.split(key)
            r = float(jax.random.uniform(kk))
            if r < min(1.0, float(p[t]) / max(float(q[t]), 1e-30)):
                n_accept += 1
            else:
                # resample from max(0, p - q) normalized
                resid = jnp.maximum(p - q, 0.0)
                resid = resid / jnp.maximum(resid.sum(), 1e-30)
                key, kk = jax.random.split(key)
                bonus = int(jax.random.categorical(
                    kk, jnp.log(resid + 1e-30)))
                break
        stats.accepted += n_accept
        toks.extend(t for t, _ in qs[:n_accept])
        if n_accept == k:       # all accepted: sample bonus from target
            key, kk = jax.random.split(key)
            bonus = int(jax.random.categorical(
                kk, jnp.log(p_all[base + k] + 1e-30)))
        toks.append(bonus)
        stats.bonus += 1
    new = toks[len(prompt):len(prompt) + max_new_tokens]
    return np.asarray(new, np.int32), stats


# -- live in-engine speculative decoding --------------------------------------

@functools.lru_cache(maxsize=8)
def _propose_fn(dcfg: ModelConfig, k: int):
    """ONE jitted executable for the k-step greedy draft scan: starting
    from each lane's pending token, decode k draft steps (writing the
    pending token and the first k-1 proposals into the draft cache) and
    return the (w, k) proposal block.  The gathered sub-cache is donated
    — the scan threads it in place."""
    def run(params, tok, cache):
        def step(carry, _):
            t, c = carry
            logits, c = api.decode_step(dcfg, params, t, c)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            return (nxt, c), nxt[:, 0]
        (_, cache), drafts = jax.lax.scan(step, (tok, cache), None, length=k)
        return jnp.swapaxes(drafts, 0, 1), cache       # (w, k)
    return jax.jit(run, donate_argnums=(2,))


@functools.lru_cache(maxsize=8)
def _verify_fn(mcfg: ModelConfig):
    """ONE jitted executable for the target verify: a k-token
    `decode_window` forward returning the target's greedy choice at every
    window position plus an all-finite health bit (the NaN guard runs on
    device so the host syncs one bool, not the logits)."""
    def run(params, window, cache):
        logits, cache = api.decode_window(mcfg, params, window, cache)
        choice = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (w, k)
        return choice, jnp.isfinite(logits).all(), cache
    return jax.jit(run, donate_argnums=(2,))


class SpecDecodeEngine(ServingEngine):
    """A ServingEngine whose decode tick is a batched propose/verify
    iteration: draft and target are CO-RESIDENT (each with a dense
    per-slot KV cache), every admitted request is prefilled into both,
    and one `step()` lands between 1 and k tokens per active slot.

    Greedy only (the engine rejects `temperature > 0` requests at
    submission): each iteration the draft proposes `k` tokens in one
    jitted scan, the target verifies the k-token window
    [pending, d_1..d_{k-1}] in one jitted `decode_window` pass, the
    longest matching prefix (capped at k-1 so the draft cache always
    holds every consumed position) is accepted, and the target's own
    argmax at the divergence point is the bonus token — so the emitted
    stream is TOKEN-EXACT vs target-only greedy decoding, the property
    `tests` and `bench_specdec`'s gate assert.  Acceptance bookkeeping
    lives in `spec_stats`.

    Restrictions (checked at construction): plain-attention transformer
    target (`transformer.window_supported`), dense un-quantized KV
    (paged growth of two coupled caches is future work).
    """

    def __init__(self, mcfg: ModelConfig, params: Params,
                 draft_cfg: ModelConfig, draft_params: Params, *,
                 k: int | None = None, **kw):
        if not transformer.window_supported(mcfg):
            raise ValueError(
                "SpecDecodeEngine needs a plain-attention transformer "
                f"target (family={mcfg.family}, use_mla={mcfg.use_mla}, "
                f"window={mcfg.window})")
        if not transformer.window_supported(draft_cfg):
            raise ValueError("draft config must be a plain-attention "
                             "transformer too")
        self.k = k if k is not None else knobs.get_int("MOZART_SPEC_K")
        if self.k < 2:
            raise ValueError(f"spec-decode needs k >= 2, got {self.k}")
        kw["paged"] = False
        kw["kv_quant"] = "0"
        super().__init__(mcfg, params, **kw)
        # the verify window writes k KV positions starting at the slot's
        # current length — finish a slot before the window would overrun
        self._headroom = self.k
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_state = state_mod.DenseKVState(
            draft_cfg, self.max_batch, self.max_len,
            decode_batch=self.decode_batch, compact=True)
        self._draft_prefill = state_mod._prefill_fn(draft_cfg, self.max_len)
        self._propose = _propose_fn(draft_cfg, self.k)
        self._verify = _verify_fn(mcfg)
        self.spec_stats = SpecStats()

    def submit(self, req: Request) -> bool:
        if req.temperature > 0.0:
            raise ValueError(
                "SpecDecodeEngine is greedy-only (temperature=0); "
                f"request {req.rid} has temperature={req.temperature}")
        return super().submit(req)

    def _dense_prefill(self, b: int, seq: np.ndarray, req: Request):
        """Prefill BOTH caches so draft and target share the context."""
        last = self.state.prefill(self._prefill, self.params, b, seq)
        self.draft_state.prefill(self._draft_prefill, self.draft_params,
                                 b, seq)
        return last

    def _advance(self, active: list[int]) -> bool:
        """One propose/verify iteration over the gathered active slots.

        Both sub-caches advance k positions on device; the host then
        rewinds each lane's index to `base + emitted` (stale KV past the
        index is never attended and is overwritten in place by later
        writes).  Padding lanes duplicate `active[0]` and are assigned
        its consumed count, so the duplicate scatter writes stay
        identical (scatter order irrelevant)."""
        k = self.k
        sel = active + [active[0]] * (self.decode_batch - len(active))
        sel_arr = jnp.asarray(sel, jnp.int32)
        with spans.span("decode", active=len(active),
                        ctx=functools.partial(self._live_positions, active)):
            tok = jnp.asarray(self.next_token[sel])
            dft_sub = _GATHER(self.draft_state.cache, sel_arr)
            drafts, dft_sub = self._propose(self.draft_params, tok, dft_sub)
            tgt_sub = _GATHER(self.state.cache, sel_arr)
            window = jnp.concatenate([tok, drafts[:, :-1]], axis=1)  # (w, k)
            choice, finite, tgt_sub = self._verify(self.params, window,
                                                   tgt_sub)
        if self.guard_nan:
            with spans.span("guard"):
                finite = bool(finite)
            self.stats["host_syncs"] += 1
            if not finite:
                self.health["nan_detected"] = True
                self.stats["nan_steps"] += 1
                return False    # sub-caches dropped: nothing scattered
        with spans.span("sample", n=len(active)):
            drafts_np = np.asarray(drafts)
            choice_np = np.asarray(choice)
            self.stats["host_syncs"] += 2
            lane = _lane_map(sel)
            consumed_by_slot: dict[int, int] = {}
            for b in active:
                j = lane[b]
                req = self.slots[b]
                n = 0
                while n < k - 1 and drafts_np[j, n] == choice_np[j, n]:
                    n += 1
                emitted = [int(t) for t in drafts_np[j, :n]] + \
                    [int(choice_np[j, n])]
                self.spec_stats.iterations += 1
                self.spec_stats.proposed += k - 1
                self.spec_stats.accepted += n
                self.spec_stats.bonus += 1
                # budget / eos truncation: a cut always finishes the slot,
                # so the dropped tail's (already written) KV is never read
                out = emitted[:req.max_new_tokens - len(req.out_tokens)]
                if self.eos_id in out:
                    out = out[:out.index(self.eos_id) + 1]
                req.out_tokens.extend(out)
                self.next_token[b, 0] = out[-1]
                self.stats["tokens_out"] += len(out)
                consumed_by_slot[b] = len(out)
                if len(req.out_tokens) >= req.max_new_tokens or \
                        out[-1] == self.eos_id:
                    self._finish(b, "eos" if out[-1] == self.eos_id
                                 else "max_new_tokens")
        consumed = jnp.asarray([consumed_by_slot[b] for b in sel],
                               jnp.int32)
        tgt_sub = {"segments": tgt_sub["segments"],
                   "index": tgt_sub["index"] - k + consumed}
        dft_sub = {"segments": dft_sub["segments"],
                   "index": dft_sub["index"] - k + consumed}
        self.state.cache = _SCATTER(self.state.cache, tgt_sub, sel_arr)
        self.draft_state.cache = _SCATTER(self.draft_state.cache,
                                          dft_sub, sel_arr)
        return True


def shared_trunk_draft(cfg: ModelConfig, params: Params, n_draft: int
                       ) -> tuple[ModelConfig, Params]:
    """A draft model = the target's first `n_draft` layers with shared
    embed / final norm / head (the standard shared-trunk draft).  Plain
    single-segment transformers only."""
    if cfg.family != "transformer" or cfg.scan_layers or \
            len(params["segments"]) != 1:
        raise ValueError("shared_trunk_draft needs a plain unscanned "
                         "single-segment transformer")
    if not 0 < n_draft < cfg.n_layers:
        raise ValueError(f"n_draft must be in (0, {cfg.n_layers})")
    seg = params["segments"][0]
    kind = next(iter(seg))
    dcfg = cfg.replace(n_layers=n_draft)
    dparams = {**{k: v for k, v in params.items() if k != "segments"},
               "segments": [
                   {kind: jax.tree.map(lambda a: a[:n_draft], seg[kind])}]}
    return dcfg, dparams


def high_tar_pair(cfg: ModelConfig, params: Params, n_draft: int
                  ) -> tuple[Params, ModelConfig, Params]:
    """(target_params, draft_cfg, draft_params) with a 100% token
    acceptance rate BY CONSTRUCTION: the target's residual-stream writes
    past layer `n_draft` are zeroed (`attn.wo` / `mlp.w_out`), so the
    deep target computes the exact same function as its first-`n_draft`-
    layer shared-trunk draft while still paying the full-depth FLOPs.

    This is the controlled experiment `bench_specdec` measures: it
    isolates the SERVING-SIDE spec-decode speedup (k tokens per verify
    pass vs one per decode step) at the paper's high-TAR operating point
    without needing trained checkpoints whose draft actually agrees."""
    dcfg, dparams = shared_trunk_draft(cfg, params, n_draft)
    seg = params["segments"][0]
    kind = next(iter(seg))
    layers = dict(seg[kind])
    attn = dict(layers["attn"])
    attn["wo"] = attn["wo"].at[n_draft:].set(0.0)
    layers["attn"] = attn
    mlp = dict(layers["mlp"])
    mlp["w_out"] = mlp["w_out"].at[n_draft:].set(0.0)
    layers["mlp"] = mlp
    tparams = {**params, "segments": [{kind: layers}]}
    return tparams, dcfg, dparams
