"""Cells found by name, a whole run on the CPU, and the output check
failing where the timed path is broken.

The tiny cells are defined only by new files and entries in a copy of
the benchmark (`conftest.add_tiny_cells`); these tests call the
harness's loader and run directly, skipping `run.py`'s look for a chip.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def run(root, name, seed, trace=False, control=False, seconds=2.0):
    cell = harness.load_cell(name, root=root)
    return harness.run(cell, seed, seconds, trace, time.perf_counter(), control=control)


def test_new_cell_is_found_by_name(tiny_root):
    cell = harness.load_cell("tiny.open", root=tiny_root)
    assert cell.chips == 1 and cell.mix["kind"] == "open_loop"
    assert cell.config["model"]["d_model"] == 64
    assert "ttft_p90_ms" in cell.e2e and "tokens_per_s" not in cell.e2e
    assert "decode_step_mfu.rate" in cell.per_layer
    with pytest.raises(harness.HarnessError):
        harness.load_cell("no.such.cell", root=tiny_root)


def test_config_file_must_state_what_the_program_runs(tiny_root):
    cell = harness.load_cell("tiny.open", root=tiny_root)
    bad = dict(cell.config, model=dict(cell.config["model"], d_ff=999))
    with pytest.raises(harness.HarnessError):
        harness.model_config(bad)


def test_split_metric_is_read_by_its_quantitys_reader(tiny_root):
    bench = tiny_root / "bench"
    assert harness.metric_reader(bench, "decode_ms.rate") is not None
    assert harness.metric_reader(bench, "decode_ms.any_later_cell") is not None
    with pytest.raises(harness.HarnessError):
        harness.metric_reader(bench, "no_such_metric.rate")


def test_gaps_by_live_counts_each_gap_by_its_steps_requests():
    times = [[0.0, 1.0, 2.0, 3.0], [1.0, 2.0], [2.2, 2.4]]
    tracks = [harness.Track(req=None, due=0.0, times=ts) for ts in times]
    rec = harness.Record(model={}, cost=None, tracks=tracks, lo=0.5, hi=2.5, setup_s=0.0)
    by_k = rec.gaps_by_live()
    assert by_k == {1: (1, pytest.approx(200.0)), 2: (3, pytest.approx(1000.0))}
    assert sum(n for n, _ in by_k.values()) == len(rec.token_gaps_s())


@pytest.mark.parametrize("seed", [2**34 + 1, 3_000_000_011])
def test_run_reports_the_cells_metrics_and_is_correct(tiny_root, seed):
    name = "tiny.open"
    r = run(tiny_root, name, seed)
    cell = harness.load_cell(name, root=tiny_root)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == set(cell.e2e)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == cell.chips
    assert list(r)[-1] == "checks"
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert 0 < r["info"]["kv_live_pct_mean"] <= r["info"]["kv_live_pct_max"] <= 100
    json.dumps(r)


def test_traced_run_reads_spans(tiny_root):
    r = run(tiny_root, "tiny.open", 9, trace=True)
    assert r["correct"]
    assert "queue_wait_p90_ms" in r["metrics"] and "host_ms_per_step.rate" in r["metrics"]
    assert r["device"]["window_s"] == pytest.approx(2.0, rel=0.1)   # the whole window
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _stale_decode(monkeypatch):
    """The decode step returns the page pool it was given: no KV is
    ever written for decoded tokens."""
    from repro.serving import paged

    real = paged.paged_decode_fn

    def factory(mcfg, quantized=False):
        fn = real(mcfg, quantized)

        def stale(params, tokens, segments, tables_sel, index_sel):
            keep = jax.tree.map(jnp.copy, segments)
            logits, _ = fn(params, tokens, segments, tables_sel, index_sel)
            return logits, keep

        return stale

    monkeypatch.setattr(paged, "paged_decode_fn", factory)


def _altered_token(monkeypatch):
    """Every fifth sampled token is replaced where it is produced."""
    from repro.serving import engine

    real, count = engine.sample, [0]

    def sample(logits, key, **kw):
        tok = real(logits, key, **kw)
        count[0] += 1
        return (tok + 1) % logits.shape[-1] if count[0] % 5 == 0 else tok

    monkeypatch.setattr(engine, "sample", sample)


@pytest.mark.parametrize("fault", [_stale_decode, _altered_token])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    r = run(tiny_root, "tiny.open", 77)
    assert not r["correct"]
    assert not r["checks"]["logit_gap"]["ok"]


@pytest.mark.parametrize("seed", [101, 2**35 + 7, 3_000_000_123])
def test_control_is_not_correct(tiny_root, seed):
    """The reference in fp8, put in the program's place and judged by the
    same checks, is not correct where the bfloat16 program is."""
    r = run(tiny_root, "tiny.open", seed, control=True)
    assert r["correct"]
    assert not r["control"]["correct"]
    assert not r["control"]["checks"]["logit_gap"]["ok"]
    assert r["control"]["checks"]["logit_gap"]["limit"] == r["checks"]["logit_gap"]["limit"]
    assert list(r)[-1] == "checks"


def test_run_py_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "internlm2-1.8b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a checkout holding only BENCHMARK.json and the benchmark's files
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "internlm2-1.8b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
