"""The one traffic generator: reads a mix file from `bench/traffic/`.

A mix fixes one sequence of (arrival gap, prompt length, output length)
from its own `base_seed`: lengths are stratified quantiles of a clipped
lognormal, gaps are stratified quantiles of the exponential (a Poisson
process at `rate_rps`), each list permuted once by the base seed.  A
run's seed draws only the prompt tokens.  So every seed offers the same
sizes at the same times, and runs of different seeds differ only as
much as two runs of one seed do, plus what the tokens change.

Kinds:
- `open_loop`: request i is due at the sum of the first i gaps, counted
  from `-ramp_s` (the ramp runs in set-up, the window starts at 0).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    idx: int
    gap: float
    prompt: np.ndarray
    max_new: int


def _lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def n_items(mix: dict, seconds: float) -> int:
    """Requests a run draws: the schedule over ramp and window, with 10 %
    to spare."""
    return int(math.ceil(mix["rate_rps"] * (mix["ramp_s"] + seconds) * 1.1)) + 8


def generate(mix: dict, seed: int, vocab: int, seconds: float, max_len: int) -> list[Item]:
    if mix["kind"] != "open_loop":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    n = n_items(mix, seconds)
    base = np.random.default_rng(mix["base_seed"])
    plen = base.permutation(_lognormal_lengths(n, mix["prompt"]))
    out = base.permutation(_lognormal_lengths(n, mix["output"]))
    out = np.minimum(out, max_len - plen)
    u = (np.arange(n) + 0.5) / n
    gaps = base.permutation(-np.log1p(-u) / mix["rate_rps"])
    rng = np.random.default_rng(int(seed))
    return [Item(idx=i, gap=float(gaps[i]),
                 prompt=rng.integers(0, vocab, size=int(plen[i])).astype(np.int32),
                 max_new=int(out[i]))
            for i in range(n)]


def prompt_range(mix: dict) -> tuple[int, int]:
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
