"""CPU tests of the benchmark: `python -m pytest bench/tests` from the repo
root."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "kv_heads": 2, "head_dim": 16,
              "d_ff": 128, "vocab": 512, "norm_eps": 1e-05}
TINY_LENGTHS = {"prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
                "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 16}}


def _write(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def add_tiny_cells(root: Path, dtype: str = "bfloat16", gap_limit: float = 0.008) -> None:
    """Define two tiny configurations and a cell of each by new files and
    entries only: `tiny.open`, a dense GQA decoder judged by the default
    reference and cost model, and `tiny-qkv.open`, a decoder with QKV
    bias (Qwen2.5's architecture) that brings its own two files, copied
    from this directory.  The gap limit sits between the tiny bf16
    program's widest gaps on CPU (0 to 0.0018 over eight seeds) and its
    fp8 control's (0.020 to 0.095)."""
    bench = root / "bench"
    for name in ("qkv_bias_reference.py", "qkv_bias_cost.py"):
        shutil.copy(TESTS / name, bench / name)
    _write(bench / "configs" / "tiny-qkv.json",
           {"name": "tiny-qkv", "arch": "qwen2.5-32b", "source": "test",
            "reference": "qkv_bias_reference.py", "cost": "qkv_bias_cost.py",
            "overrides": dict(TINY_MODEL, dtype=dtype, param_dtype=dtype),
            "model": dict(TINY_MODEL, swiglu=True, qkv_bias=True, tie_embeddings=False,
                          rope_theta=1000000.0, dtype=dtype)})
    _write(bench / "cells" / "tiny-qkv.open.json",
           {"slots": 4, "max_len": 64, "page_size": 8,
            "check": {"requests": 6, "min_tokens": 10, "max_logit_gap": gap_limit}})
    model = dict(TINY_MODEL, swiglu=True, tie_embeddings=True, rope_theta=10000.0, dtype=dtype)
    _write(bench / "configs" / "tiny.json",
           {"name": "tiny", "arch": "smollm-135m", "source": "test",
            "overrides": dict(TINY_MODEL, dtype=dtype, param_dtype=dtype), "model": model})
    _write(bench / "traffic" / "tiny-open.json",
           {"kind": "open_loop", "rate_rps": 20, "ramp_s": 1, "base_seed": 5, **TINY_LENGTHS})
    _write(bench / "cells" / "tiny.open.json",
           {"slots": 4, "max_len": 64, "page_size": 8,
            "check": {"requests": 6, "min_tokens": 10, "max_logit_gap": gap_limit}})
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only: the CPU has no published peak here")
    _write(bench / "peaks.json", peaks)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for config in ("tiny", "tiny-qkv"):
        spec["configs"].append({"name": config, "source": "test", "reduced": ["n_layers"],
                                "file": f"bench/configs/{config}.json", "why": "test"})
        spec["workloads"].append({"name": f"{config}.open", "config": config,
                                  "traffic": "tiny-open", "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if "internlm2-1.8b.chat" in m["workloads"]:
            m["workloads"] += ["tiny.open", "tiny-qkv.open"]
    _write(root / "BENCHMARK.json", spec)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark with the tiny cells added."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    add_tiny_cells(root)
    return root
