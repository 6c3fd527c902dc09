"""Parameter counts, KV bytes and the peaks table."""

import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# HuggingFaceTB/SmolLM-135M at its published widths (no cell runs it yet)
SMOLLM_135M = {"n_layers": 30, "d_model": 576, "n_heads": 9, "kv_heads": 3, "head_dim": 64,
               "d_ff": 1536, "vocab": 49152, "swiglu": True, "tie_embeddings": True,
               "dtype": "bfloat16", "param_dtype": "bfloat16"}


def model(name):
    if name == "smollm-135m":
        return SMOLLM_135M
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_parameter_counts():
    assert flops.param_count(model("smollm-135m")) / 1e6 == pytest.approx(134.5, abs=0.05)
    assert flops.param_count(model("internlm2-1.8b")) / 1e9 == pytest.approx(1.889, abs=0.0005)


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(model("smollm-135m")) == 23_040
    assert flops.kv_bytes_per_token(model("internlm2-1.8b")) == 98_304


def test_decode_needs_weights_and_live_kv_only():
    m = model("internlm2-1.8b")
    f1, b1 = flops.decode_cost(m, 1, 100)
    f2, b2 = flops.decode_cost(m, 2, 200)
    assert b2 - b1 == pytest.approx(flops.kv_bytes_per_token(m) * 100 + m["d_model"] * 2)
    # one token: ~2 flops per matmul weight, plus attention over its context
    assert f1 == pytest.approx(2 * (flops.param_count(m) - m["vocab"] * m["d_model"]), rel=0.01)


def test_prefill_counts_one_row_of_logits():
    m = model("smollm-135m")
    f, _ = flops.prefill_cost(m, 1)
    assert f == pytest.approx(2 * (m["n_layers"] * flops.layer_matmul_params(m) + m["d_model"] * m["vocab"])
                              + flops._attn_flops(m, 1))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_least_time_is_the_larger_bound():
    p = flops.peaks("TPU v5 lite")
    assert flops.least_time(197e12, 1.0, p) == pytest.approx(1.0)
    assert flops.least_time(1.0, 819e9, p) == pytest.approx(1.0)
