"""The serving engine's spans, counters and program names.

Spans are off by default and then cost one shared no-op object; on, a
profiled run writes `serve.*` spans nested in `serve.step`.  The
`host_syncs` counter and the `t_admit` stamp are checked on fixed
scripts, and the device programs carry stable names.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.models.config import ModelConfig
from repro.serving import paged, resilience, sampling, spans
from repro.serving import state as state_mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.specdec import SpecDecodeEngine, shared_trunk_draft

ROOT = Path(__file__).resolve().parents[1]

TINY = ModelConfig(
    name="tiny-spans",
    n_layers=2,
    d_model=32,
    n_heads=4,
    kv_heads=2,
    head_dim=8,
    d_ff=64,
    vocab=61,
    dtype="float32",
    param_dtype="float32",
    scan_layers=False,
)


@pytest.fixture(scope="module")
def params():
    return api.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def spans_off():
    spans.enable(False)
    yield
    spans.enable(False)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, TINY.vocab - 1, size=n).astype(np.int32)


def _engine(params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 16)
    return ServingEngine(TINY, params, guard_nan=True, **kw)


class _Refused:
    def __init__(self, *a, **k):
        raise AssertionError("a TraceAnnotation was built with spans off")


def test_span_off_is_the_shared_no_op_and_computes_nothing(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    called = []
    sp = spans.span("decode", active=3, ctx=lambda: called.append(1))
    assert sp is spans.NO_SPAN and not spans.enabled()
    with sp as inner:
        inner.set_metadata(admitted=2)
    assert called == []


def test_span_on_builds_an_annotation_with_evaluated_args(monkeypatch):
    built = []

    class Record:
        def __init__(self, name, **args):
            built.append((name, args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Record)
    spans.enable(True)
    assert spans.enabled()
    spans.span("decode", active=3, ctx=lambda: 41)
    assert built == [("serve.decode", {"active": 3, "ctx": 41})]


def test_engine_with_spans_off_builds_no_span_and_no_span_args(params, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)

    def refused(*a, **k):
        raise AssertionError("a span argument was computed with spans off")

    monkeypatch.setattr(ServingEngine, "_live_positions", refused)
    monkeypatch.setattr(ServingEngine, "_bucket", refused)
    eng = _engine(params)
    reqs = [Request(rid=i, prompt=_prompt(5 + i, i), max_new_tokens=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for p in ProfileData.from_file(str(path)).planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_profiled_run_writes_nested_spans_with_their_args(params, tmp_path):
    eng = _engine(params)
    reqs = [Request(rid=10 + i, prompt=_prompt(5 + 7 * i, i), max_new_tokens=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    with spans.profile(str(tmp_path)):
        eng.run()
    assert not spans.enabled()
    found = _host_spans(tmp_path)
    by = {}
    for name, s, e, args in found:
        by.setdefault(name, []).append((s, e, args))
    want = {
        "serve.step": {"live", "active"},
        "serve.admit": {"queued", "admitted"},
        "serve.prefill": {"rid", "tokens", "bucket", "resumed"},
        "serve.grow": {"preempted"},
        "serve.decode": {"active", "ctx", "pages"},
        "serve.sample": {"n"},
    }
    assert set(by) == set(want)
    for name, keys in want.items():
        assert all(set(args) == keys for _, _, args in by[name]), name
    steps = by["serve.step"]
    assert len(steps) == eng.stats["decode_steps"]
    for name in want:
        if name != "serve.step":
            for s, e, _ in by[name]:
                assert any(a <= s and e <= b for a, b, _ in steps), name
    prefills = sorted(by["serve.prefill"], key=lambda x: x[0])
    assert [int(a["rid"]) for _, _, a in prefills] == [10, 11, 12]
    assert [int(a["tokens"]) for _, _, a in prefills] == [5, 12, 19]
    assert [int(a["bucket"]) for _, _, a in prefills] == [16, 16, 32]
    assert {int(a["admitted"]) for _, _, a in by["serve.admit"]} == {0, 1, 2}
    assert sum(int(a["n"]) for _, _, a in by["serve.sample"]) == sum(
        int(a["active"]) for _, _, a in by["serve.decode"])
    assert sum(int(a["pages"]) for _, _, a in by["serve.decode"]) == \
        eng.pool.stats["kv_pages_read"]


def test_host_syncs_count_every_read_of_a_device_value(params):
    """Two requests fill both slots: two first-token samples, then two
    steps that each read their two tokens and the guard at once; a third
    request is admitted once they finish (one sample) and decodes one
    step alone.  One read per admission and one per decode step, with the
    guard on or off."""
    for guard in (True, False):
        eng = _engine(params)
        eng.guard_nan = guard
        for i, n in enumerate((3, 3, 2)):
            eng.submit(Request(rid=i, prompt=_prompt(6, i), max_new_tokens=n))
        eng.step()
        assert eng.stats["host_syncs"] == 2 + 1
        eng.step()
        assert eng.stats["host_syncs"] == 3 + 1
        eng.step()
        assert eng.stats["host_syncs"] == 4 + 1 + 1
        assert eng.stats["decode_steps"] == 3 and not eng.queue
        assert eng.stats["live_slot_steps"] == 2 + 2 + 1
        assert eng.stats["host_syncs"] == eng.stats["prefills"] + eng.stats["decode_steps"]


def test_spec_decode_counts_its_guard_and_two_reads_per_step(params):
    dcfg, dparams = shared_trunk_draft(TINY, params, 1)
    eng = SpecDecodeEngine(TINY, params, dcfg, dparams, k=3, max_batch=2, max_len=64,
                           guard_nan=True)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=_prompt(6, i), max_new_tokens=7))
    eng.run()
    assert eng.stats["decode_steps"] > 0
    assert eng.stats["host_syncs"] == eng.stats["prefills"] + 3 * eng.stats["decode_steps"]


def test_t_admit_is_stamped_once_and_kept_across_preemption(params, monkeypatch):
    """A pool far too small for the load preempts; a resumed request keeps
    the stamp of its first prefill."""
    at_preempt = {}
    real = ServingEngine._preempt

    def preempt(self, b):
        req = self.slots[b]
        at_preempt.setdefault(req.rid, req.t_admit)
        real(self, b)

    monkeypatch.setattr(ServingEngine, "_preempt", preempt)
    eng = _engine(params, max_batch=4, num_pages=9)
    reqs = [Request(rid=i, prompt=_prompt(n, i), max_new_tokens=16)
            for i, n in enumerate((20, 30, 25, 18, 22, 27))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.stats["preemptions"] > 0 and at_preempt
    for r in reqs:
        assert r.done and r.t_submit <= r.t_admit <= r.t_first
    for r in reqs:
        if r.rid in at_preempt:
            assert r.t_admit == at_preempt[r.rid]


def test_programs_have_stable_names(params):
    eng = _engine(params)
    assert paged.paged_decode_fn(TINY).__name__ == "paged_decode"
    assert paged.paged_decode_fn(TINY, True).__name__ == "paged_decode_int8"
    assert paged.paged_prefill_fn(TINY, 16, 16).__name__ == "paged_prefill"
    assert paged.paged_prefill_fn(TINY, 16, 16, True).__name__ == "paged_prefill_int8"
    assert state_mod._decode_fn(TINY).__name__ == "decode"
    assert state_mod._prefill_fn(TINY, 64).__name__ == "prefill"
    pool = eng.pool
    args = (params, jnp.zeros((2, 1), jnp.int32), pool.segments, pool.tables, pool.index)
    text = paged.paged_decode_fn(TINY).lower(*args).as_text(debug_info=True)
    assert "module @jit_paged_decode " in text
    assert "kv_write" in text and "kv_gather" not in text
    heads = api.init_paged_cache(TINY, pool.num_pages, pool.page_size, rows=False)
    args = args[:2] + (heads,) + args[3:]
    text = paged.gathered_decode_fn(TINY).lower(*args).as_text(debug_info=True)
    assert "module @jit_paged_decode " in text
    assert all(s in text for s in ("kv_gather", "kv_scatter"))
    text = paged.paged_prefill_fn(TINY, 16, 16).lower(
        params, np.zeros((1, 16), np.int32), 5, pool.segments, pool.table_row(0, 1)).as_text()
    assert "module @jit_paged_prefill " in text
    text = resilience._ALL_FINITE.lower(jnp.ones((2, 3))).as_text()
    assert "module @jit_logits_finite " in text
    text = sampling.sample_tokens.lower(
        jnp.ones((2, 1, 3)), np.zeros(2, np.int32), np.zeros(2, np.float32), np.int32(1),
        jax.random.PRNGKey(0)).as_text()
    assert "module @jit_sample_tokens " in text


def test_serve_profile_writes_the_engine_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(ROOT / "src") + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = tmp_path / "profile"
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-135m", "--smoke",
         "--requests", "2", "--max-new", "3", "--max-len", "64", "--profile", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    names = {name for name, *_ in _host_spans(out)}
    assert {"serve.step", "serve.decode", "serve.sample"} <= names
