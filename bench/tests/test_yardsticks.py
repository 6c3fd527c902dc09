"""A configuration names the plain reference and the cost model it is
judged by; one of another architecture brings them as files of its own.

The second tiny configuration (`tiny-qkv`, Qwen2.5's QKV bias at tiny
widths; `conftest.add_tiny_cells`) names a bias-aware reference and cost
model copied in from this directory."""

import json
import math
import shutil
import time

import jax
import numpy as np
import pytest

from bench import flops, harness, weights
from bench import trace_reduce as tr
from test_program_spans import BENCH, served, tracks


def run(cell, seed, control=False):
    return harness.run(cell, seed, 2.0, False, time.perf_counter(), control=control)


@pytest.fixture
def edit_config(tiny_root, tmp_path):
    """A copy of the tiny benchmark with `tiny-qkv`'s configuration file
    edited by `change(config)`."""

    def make(change):
        root = tmp_path / "root"
        shutil.copytree(tiny_root, root)
        path = root / "bench" / "configs" / "tiny-qkv.json"
        config = json.loads(path.read_text())
        change(config)
        path.write_text(json.dumps(config))
        return root

    return make


def test_cell_loads_the_yardsticks_its_configuration_names(tiny_root):
    dense = harness.load_cell("tiny.open", root=tiny_root)
    qkv = harness.load_cell("tiny-qkv.open", root=tiny_root)
    assert dense.reference.__file__ == str((tiny_root / "bench" / "reference.py").resolve())
    assert dense.cost.__file__ == str((tiny_root / "bench" / "flops.py").resolve())
    assert qkv.reference.__file__ == str((tiny_root / "bench" / "qkv_bias_reference.py").resolve())
    assert qkv.cost.__file__ == str((tiny_root / "bench" / "qkv_bias_cost.py").resolve())
    # once per path in a process, so a reference's jitted program compiles once
    assert harness.load_cell("tiny.open", root=tiny_root).reference is dense.reference
    assert harness.load_cell("internlm2-1.8b.chat").reference is not dense.reference


@pytest.mark.parametrize("key, name", [("reference", "no_such_reference.py"),
                                       ("cost", "no_such_cost.py"),
                                       ("reference", "cells/tiny-qkv.open.json"),
                                       ("cost", "../BENCHMARK.json")])
def test_a_name_that_does_not_resolve_is_an_error(edit_config, key, name):
    root = edit_config(lambda config: config.update({key: name}))
    with pytest.raises(harness.HarnessError):
        harness.load_cell("tiny-qkv.open", root=root)


def test_qkv_bias_cell_is_correct_by_its_own_reference(tiny_root):
    r = run(harness.load_cell("tiny-qkv.open", root=tiny_root), 2**33 + 17, control=True)
    assert r["correct"], r["checks"]
    assert not r["control"]["correct"]
    assert not r["control"]["checks"]["logit_gap"]["ok"]


def test_qkv_bias_cell_fails_by_the_dense_reference(edit_config, tiny_root):
    """Without its `reference` key the configuration falls to the dense
    reference, which leaves the biases out: the run is refused before its
    window, and on the tokens the bias-aware reference decodes greedily
    the dense one's widest gap exceeds the cell's limit."""
    root = edit_config(lambda config: config.pop("reference"))
    cell = harness.load_cell("tiny-qkv.open", root=root)
    assert cell.reference.__file__ == str((root / "bench" / "reference.py").resolve())
    with pytest.raises(harness.HarnessError, match="attn/bq"):
        run(cell, 2**33 + 17)

    own = harness.load_cell("tiny-qkv.open", root=tiny_root)
    model, limit = own.config["model"], own.geometry["check"]["max_logit_gap"]
    w = weights.make(harness.model_config(own.config), 2**33 + 17)
    prompt = np.random.default_rng(3).integers(0, model["vocab"], 24).astype(np.int32)
    served = []
    with jax.default_matmul_precision("highest"):
        for _ in range(12):
            seq = np.concatenate([prompt, np.asarray(served, np.int32)])
            served.append(int(np.argmax(own.reference.forward(model, w, seq)[-1])))
    assert own.reference.served_gaps(model, w, prompt, served, 64).max() == pytest.approx(0, abs=1e-6)
    assert cell.reference.served_gaps(model, w, prompt, served, 64).max() > limit


@pytest.mark.parametrize("name", ["internlm2-1.8b.chat", "tiny.open", "tiny-qkv.open"])
def test_each_reference_reads_every_leaf_of_its_weights(tiny_root, name):
    cell = harness.load_cell(name, root=tiny_root)
    harness.check_reads(cell.reference, _shapes(harness.model_config(cell.config)))


def _expected(rec, cost):
    """`decode_step_mfu` and `prefill_step_mfu` of `rec` by `cost`'s
    counts, summed here span by span."""
    m = rec.model
    out = []
    for kind, count in (("decode", lambda a: cost.decode_cost(m, a["active"], a["ctx"])),
                        ("prefill", lambda a: cost.prefill_cost(m, a["tokens"]))):
        spans = rec.spans(kind)
        dev = sum(rec.span_device_ns(s) for s in spans) * 1e-9
        out.append(100.0 * sum(flops.least_time(*count(s[3]), rec.peak) for s in spans) / dev)
    return tuple(out)


def test_mfu_readers_divide_by_the_configurations_cost_model(tiny_root):
    trace = tr.from_planes(iter(served(False)), "tpu")
    readers = [harness.metric_reader(BENCH, n) for n in ("decode_step_mfu.rate", "prefill_step_mfu")]

    def read(cell):
        rec = harness.Record(model=cell.config["model"], cost=cell.cost, tracks=tracks(), lo=0.0,
                             hi=10.0, setup_s=1.0, peak=flops.peaks("TPU v5 lite"), trace=trace)
        return rec, tuple(r(rec) for r in readers)

    # the dense configuration reads exactly what `bench/flops.py` gives
    rec, got = read(harness.load_cell("internlm2-1.8b.chat"))
    assert got == _expected(rec, flops)
    # the other reads what its own cost model gives, which counts more
    qkv = harness.load_cell("tiny-qkv.open", root=tiny_root)
    rec, got = read(qkv)
    assert got == _expected(rec, qkv.cost)
    assert all(a > b for a, b in zip(got, _expected(rec, flops)))


def _std_before(path, shape, n_layers):
    """`weights._std` as it was before per-layer vectors had their own."""
    if path.endswith("scale"):
        return 0.1
    if path.startswith("embed") or path.startswith("head"):
        return 0.02
    std = 1.0 / math.sqrt(shape[-2])
    if path.endswith("wo") or path.endswith("w_out"):
        std /= math.sqrt(2 * n_layers)
    return std


def _shapes(cfg):
    from repro.models import api

    return jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))


def _leaves(cfg):
    shapes = _shapes(cfg)
    return list(zip(weights.leaf_paths(shapes), (sd.shape for sd in jax.tree.leaves(shapes))))


def test_weights_spread_as_before_for_the_dense_cell():
    cell = harness.load_cell("internlm2-1.8b.chat")
    cfg = harness.model_config(cell.config)
    leaves = _leaves(cfg)
    assert len(leaves) == 12
    for path, shape in leaves:
        want = _std_before(path, shape, cfg.n_layers)
        assert weights._std(path, shape, cfg.n_layers) == want, path


def test_a_vector_per_layer_gets_its_own_spread(tiny_root):
    cell = harness.load_cell("tiny-qkv.open", root=tiny_root)
    cfg = harness.model_config(cell.config)
    biases = [(p, s) for p, s in _leaves(cfg) if p.split("/")[-1] in ("bq", "bk", "bv")]
    assert len(biases) == 3
    for path, shape in biases:
        assert shape[0] == cfg.n_layers and len(shape) == 2
        assert weights._std(path, shape, cfg.n_layers) == weights.VECTOR_STD
