"""Every seed gets the same sizes at the same times; seeds differ in tokens."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("seeds", [(2**33 + 1, 3_000_000_007), (0, 17)])
def test_seeds_share_sizes_and_times_and_differ_in_tokens(seeds):
    spec = json.loads((TRAFFIC / "chat.json").read_text())
    a, b = (loadgen.generate(spec, s, 1000, 51, 2048) for s in seeds)
    assert len(a) == len(b) == loadgen.n_items(spec, 51)
    assert [(len(i.prompt), i.max_new, i.gap) for i in a] == [(len(i.prompt), i.max_new, i.gap) for i in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = loadgen.generate(spec, seeds[0], 1000, 51, 2048)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    lo, hi = loadgen.prompt_range(spec)
    assert all(lo <= len(i.prompt) <= hi and len(i.prompt) + i.max_new <= 2048 for i in a)


def test_lengths_have_the_mix_medians():
    spec = json.loads((TRAFFIC / "chat.json").read_text())
    items = loadgen.generate(spec, 5, 1000, 2000, 2048)
    assert np.median([len(i.prompt) for i in items]) == pytest.approx(spec["prompt"]["median"], rel=0.02)
    assert np.median([i.max_new for i in items]) == pytest.approx(spec["output"]["median"], rel=0.02)


def test_unknown_kind_raises():
    with pytest.raises(ValueError):
        loadgen.generate({"kind": "closed_loop"}, 1, 1000, 51, 2048)


def test_open_loop_rate_is_the_mix_rate():
    spec = json.loads((TRAFFIC / "chat.json").read_text())
    items = loadgen.generate(spec, 5, 1000, 51, 2048)
    assert np.mean([i.gap for i in items]) == pytest.approx(1 / spec["rate_rps"], rel=0.05)
