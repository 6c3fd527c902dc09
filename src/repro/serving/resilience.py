"""SLO-aware resilience primitives for the serving cluster.

The paper's two deployment stories are exactly the settings where
failures and overload are the norm: datacenter LLM serving (fig10's
energy numbers assume sustained traffic through a fleet) and AV
perception under hard per-request deadlines (fig12).  A throughput
number measured on a cluster that crashes on total outage, never sheds,
and cannot detect a wedged or NaN-emitting replica is not a number you
can trust under churn.  This module holds the pieces
`serving.cluster.ServingCluster` threads through its step loop:

* **NaN/Inf guard** — `logits_finite` is a cheap jitted all-finite
  reduction over a decode's logits.  The engine computes the same
  reduction inside its sampling program and reads it with the tokens, so
  a corrupted KV page (HBM bit flip, bad kernel) can never leak garbage
  tokens into a request's stream: the engine raises its
  ``health["nan_detected"]`` flag and emits nothing, and the cluster
  watchdog quarantines the replica that same step.
* **`Watchdog`** — per-replica liveness tracking: a replica that holds
  work (queued or in-flight requests) but has not emitted a token for
  `stall_steps` cluster steps is quarantined exactly like
  `kill_replica` (token-exact requeue of everything it held), as is a
  replica whose engine flagged non-finite logits.
* **`ChaosSchedule`** — a seeded, deterministic fault script
  (kill / restart / stall / unstall / nan events at fixed step offsets)
  the chaos benchmark replays against a live cluster; `generate` draws a
  schedule from a seed, or build one from explicit `ChaosEvent`s.
* **`inject_nan`** — the nan event's implementation: poisons one live
  KV page (scales for int8 pools, the dense slot slab otherwise) so the
  next decode over it produces non-finite logits — a transient data
  corruption the guard + requeue path must recover from token-exactly.
* **goodput** — `goodput_tokens` counts only tokens of requests that
  finished within their deadline (no deadline = always counted); tokens
  of deadline-missing, shed, poison, or rejected requests are NOT
  goodput, which is what the chaos gate holds above a fraction of the
  fault-free run.

Everything here is host-side and duck-typed against the engine/cluster
(no imports from them), so `engine.py` and `cluster.py` can both import
this module without cycles.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import knobs

def _all_finite(x):
    return jnp.isfinite(x).all()


# one executable per logits shape (decode width is fixed in steady
# state), reused across engines via the module-level jit cache; profiles
# show it as `jit_logits_finite`
_all_finite.__name__ = "logits_finite"
_ALL_FINITE = jax.jit(_all_finite)


def logits_finite(logits) -> bool:
    """True iff every logit is finite — the decode-output health guard.
    Jitted scalar reduction: the host syncs on one bool, not the array."""
    return bool(_ALL_FINITE(logits))


def goodput_tokens(reqs) -> int:
    """Tokens of requests that completed WITHIN their deadline.

    Shed / poison / rejected requests contribute nothing, and neither
    does a request that finished past its deadline — late tokens are
    wasted work, not goodput.  Requests without a deadline count fully.
    """
    total = 0
    for r in reqs:
        if r.t_done is None or r.finish_reason in ("shed", "poison", "rejected"):
            continue
        if r.deadline_s is not None and (r.t_done - r.t_submit) > r.deadline_s:
            continue
        total += len(r.out_tokens)
    return total


def goodput_violations(reqs) -> int:
    """Requests whose tokens `goodput_tokens` would count despite having
    missed their deadline — an independent recount the chaos gate pins
    at zero (a nonzero value means the goodput accounting is broken)."""
    bad = 0
    for r in reqs:
        if r.t_done is None or r.finish_reason in ("shed", "poison", "rejected"):
            continue
        if r.deadline_s is None:
            continue
        counted = (r.t_done - r.t_submit) <= r.deadline_s
        missed = (r.t_done - r.t_submit) > r.deadline_s
        if counted and missed:
            bad += 1
    return bad


class Watchdog:
    """Detects replicas that hold work but make no progress.

    `check` is called once per cluster step per healthy replica and
    returns a quarantine reason ("nan" / "stall") or None.  Progress is
    token emission: a replica with queued or in-flight requests whose
    `tokens_out` counter has not moved for `stall_steps` consecutive
    checks is stalled (covers wedged hosts, livelocked admission, and
    chaos-injected stalls alike).  An engine whose decode emitted
    non-finite logits flags itself; the watchdog surfaces that flag the
    same step so no further decodes run on the sick replica.
    """

    def __init__(
        self, n_replicas: int, *, stall_steps: int | None = None, nan_check: bool | None = None
    ):
        self.stall_steps = (
            stall_steps
            if stall_steps is not None
            else knobs.get_int("MOZART_WATCHDOG_STALL_STEPS")
        )
        self.nan_check = (
            nan_check if nan_check is not None else knobs.get_bool("MOZART_WATCHDOG_NAN")
        )
        self._last_tokens = [0] * n_replicas
        self._idle = [0] * n_replicas
        self.events: list[tuple[int, int, str]] = []  # (step, replica, reason)

    def reset(self, i: int) -> None:
        """Forget replica `i`'s history (call after a restart rebuilds
        its engine — the fresh engine's counters start at zero)."""
        self._last_tokens[i] = 0
        self._idle[i] = 0

    def check(self, i: int, eng) -> str | None:
        if self.nan_check and eng.health.get("nan_detected"):
            return "nan"
        tokens = eng.stats["tokens_out"]
        has_work = bool(eng.queue) or any(s is not None for s in eng.slots)
        if not has_work or tokens > self._last_tokens[i]:
            self._last_tokens[i] = tokens
            self._idle[i] = 0
            return None
        self._idle[i] += 1
        if self._idle[i] >= self.stall_steps:
            return "stall"
        return None


def inject_nan(eng) -> bool:
    """Poison one live KV page of `eng` (transient-corruption chaos).

    Targets the first page owned by the first live slot so the very next
    decode over that slot attends through NaN and produces non-finite
    logits.  Int8 pools cannot hold a NaN, so their per-page SCALES are
    poisoned instead (the dequantized gather then carries the NaN).
    Returns False (no-op) when the engine holds no live slot to poison.
    """
    live = [b for b, r in enumerate(eng.slots) if r is not None]
    if not live:
        return False
    if eng.paged:
        pages = eng.pool.owned(live[0])
        if not pages:
            return False
        p = pages[0]
        if eng.pool.quant:
            eng.pool.scales = jax.tree.map(lambda s: s.at[:, p].set(jnp.nan), eng.pool.scales)
        else:
            eng.pool.segments = jax.tree.map(lambda a: a.at[:, p].set(jnp.nan), eng.pool.segments)
    else:
        b = live[0]
        state = getattr(eng, "state", None)
        if state is not None and getattr(state, "quantized", False):
            # int8 rectangles: poison the slot's scales, like quant pools
            state.scales = jax.tree.map(lambda s: s.at[:, b].set(jnp.nan), state.scales)
        elif "segments" in eng.cache:
            # transformer dense rectangles: leaves are (L, B, C, ...)
            eng.cache["segments"] = jax.tree.map(
                lambda a: a.at[:, b].set(jnp.nan) if a.ndim >= 2 else a, eng.cache["segments"]
            )
        else:
            # recurrent / cross-attn layers layout: batch on axis 0
            eng.cache["layers"] = jax.tree.map(
                lambda a: a.at[b].set(jnp.nan) if a.ndim >= 1 else a, eng.cache["layers"]
            )
    return True


CHAOS_KINDS = ("kill", "restart", "stall", "unstall", "nan")


@dataclasses.dataclass(frozen=True, order=True)
class ChaosEvent:
    """One scripted fault: at cluster step `step`, do `kind` to
    `replica`.  Ordering is (step, replica, kind) so a schedule sorts
    deterministically."""

    step: int
    replica: int
    kind: str

    def __post_init__(self):
        if self.kind not in CHAOS_KINDS:
            raise ValueError(f"unknown chaos kind {self.kind!r}; pick one of {CHAOS_KINDS}")


class ChaosSchedule:
    """A deterministic fault script replayed against a live cluster.

    `apply(cluster, step)` fires every event whose step offset has come
    due (events are keyed to `cluster.stats['steps']`, not wall clock,
    so a fixed schedule reproduces exactly regardless of host speed).
    Build one from explicit events, or `generate` a seeded random script
    — same seed, same events, every time.
    """

    def __init__(self, events):
        self.events: list[ChaosEvent] = sorted(events)
        self._i = 0
        self.fired: list[tuple[int, ChaosEvent]] = []

    @property
    def pending(self) -> bool:
        return self._i < len(self.events)

    def apply(self, cluster, step: int) -> list[ChaosEvent]:
        """Fire all events due at or before `step`; returns them."""
        fired: list[ChaosEvent] = []
        while self._i < len(self.events) and self.events[self._i].step <= step:
            ev = self.events[self._i]
            self._i += 1
            if ev.kind == "kill":
                cluster.kill_replica(ev.replica)
            elif ev.kind == "restart":
                cluster.restart_replica(ev.replica)
            elif ev.kind == "stall":
                cluster.stall_replica(ev.replica)
            elif ev.kind == "unstall":
                cluster.unstall_replica(ev.replica)
            elif ev.kind == "nan":
                inject_nan(cluster.replicas[ev.replica])
            self.fired.append((step, ev))
            fired.append(ev)
        return fired

    @classmethod
    def generate(
        cls,
        seed: int | None = None,
        *,
        n_replicas: int,
        horizon: int,
        kills: int = 1,
        stalls: int = 1,
        nans: int = 1,
        restart_after: int = 12,
    ) -> "ChaosSchedule":
        """Seeded random fault script over `horizon` cluster steps.

        Each kill and stall is paired with a recovery (`restart` /
        `unstall`) `restart_after` steps later, and at least one replica
        is always left untouched per event so the schedule alone cannot
        wedge the whole fleet (total outage is a deliberate drill, not a
        dice roll).  One rng drives every draw: the seed pins the script.
        """
        rng = np.random.default_rng(knobs.get_int("MOZART_CHAOS_SEED") if seed is None else seed)
        events: list[ChaosEvent] = []
        span = max(horizon - restart_after - 1, 1)
        for kind, reco, n in (("kill", "restart", kills), ("stall", "unstall", stalls)):
            for _ in range(n):
                step = int(rng.integers(1, span + 1))
                replica = int(rng.integers(0, max(n_replicas - 1, 1)))
                events.append(ChaosEvent(step, replica, kind))
                events.append(ChaosEvent(step + restart_after, replica, reco))
        for _ in range(nans):
            step = int(rng.integers(1, span + 1))
            replica = int(rng.integers(0, max(n_replicas - 1, 1)))
            events.append(ChaosEvent(step, replica, "nan"))
            # the watchdog quarantines the poisoned replica; schedule
            # its recovery so the script converges back to full health
            events.append(ChaosEvent(step + restart_after, replica, "restart"))
        return cls(events)
