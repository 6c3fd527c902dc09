"""The serving program's own spans, program names, scopes and stamps
against the benchmark: what the accepted readers read stays the same, and
`admit_wait_p90_ms` reads the program's admission stamps."""

import json
import time
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import flops, harness
from bench import trace_reduce as tr

BENCH = Path(__file__).resolve().parents[1]
DECODE = "jit(paged_decode)/jit(main)/"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def op(name, start, dur, scope, program):
    # a TPU trace ends each op's scope path with ":"
    return ev(name, start, dur, **({"tf_op": scope + ":"} if program else {}))


def served(program):
    """One prefill and two decode steps as the benchmark's wrappers trace
    them, alone (`program` False: programs named `jit_fn`, no scopes) or
    with the serving program's spans, program names and scope paths."""
    host = [ev("bench.window", 0, 200),
            ev("bench.step", 5, 90), ev("bench.prefill", 8, 22, tokens=500),
            ev("bench.decode", 42, 26, active=2, ctx=1000),
            ev("bench.step", 100, 90), ev("bench.decode", 103, 36, active=2, ctx=1002)]
    if program:
        host += [ev("serve.step", 6, 88, live=2, active=2),
                 ev("serve.admit", 6, 34, queued=1, admitted=1),
                 ev("serve.prefill", 7, 33, rid=3, tokens=500, bucket=512, resumed=0),
                 ev("serve.grow", 40, 1, preempted=0),
                 ev("serve.decode", 41, 29, active=2, ctx=1000), ev("serve.guard", 70, 2),
                 ev("serve.sample", 72, 22, n=2),
                 ev("serve.step", 101, 88, live=2, active=2),
                 ev("serve.decode", 102, 38, active=2, ctx=1002), ev("serve.guard", 140, 1),
                 ev("serve.sample", 141, 48, n=2)]
    name = {True: ("jit_paged_prefill", "jit_paged_decode", "jit_logits_finite"),
            False: ("jit_fn", "jit_fn", "jit__lambda_")}[program]
    modules = [ev(name[0], 10, 18), ev(name[1], 45, 21), ev(name[2], 70, 1), ev(name[1], 105, 30)]
    ops = [op("fusion.0", 10, 18, "jit(paged_prefill)/jit(main)/dot_general", program),
           op("while.1", 45, 21, DECODE + "while", program),
           op("gather.2", 45, 5, DECODE + "kv_gather/gather", program),
           op("dus.3", 52, 2, DECODE + "while/body/kv_write/dynamic_update_slice", program),
           op("scatter.4", 62, 4, DECODE + "kv_scatter/scatter", program),
           op("reduce.5", 70, 1, "jit(logits_finite)/jit(main)/reduce_and", program),
           op("while.1", 105, 30, DECODE + "while", program),
           op("scatter.4", 130, 5, DECODE + "kv_scatter/scatter", program)]
    return [plane("/host:CPU", {"python": host}),
            plane("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops})]


def tracks(with_admit=True):
    def req(t_submit, t_admit):
        r = NS(done=True, finish_reason="max_new_tokens", t_submit=t_submit)
        if with_admit:
            r.t_admit = t_admit
        return r

    return [harness.Track(req(100.0, 100.05), due=1.0, submit=1.01, times=[1.5, 1.7, 1.9],
                          prefill_at=1.06),
            harness.Track(req(200.0, 200.2), due=2.0, submit=2.0, times=[2.4, 2.6],
                          prefill_at=2.2),
            harness.Track(req(300.0, None), due=9.0, submit=9.0, times=[]),
            harness.Track(req(400.0, None), due=11.0, submit=float("nan"), times=[])]


def record(trace, tracks):
    model = json.loads((BENCH / "configs" / "internlm2-1.8b.json").read_text())["model"]
    return harness.Record(model=model, cost=harness.yardstick(BENCH, "flops.py"), tracks=tracks,
                          lo=0.0, hi=10.0, setup_s=1.0, peak=flops.peaks("TPU v5 lite"),
                          trace=trace)


def accepted_metrics():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return [m["name"] for m in spec["end_to_end"] + spec["per_layer"] if m["name"] != "admit_wait_p90_ms"]


@pytest.mark.parametrize("name", accepted_metrics())
def test_accepted_reader_reads_the_same_with_the_programs_spans(name):
    read = harness.metric_reader(BENCH, name)
    bare = read(record(tr.from_planes(iter(served(False)), "tpu"), tracks(with_admit=False)))
    assert bare is not None
    assert read(record(tr.from_planes(iter(served(True)), "tpu"), tracks())) == bare


def test_breakdown_is_the_same_with_the_programs_spans():
    bare, full = (tr.from_planes(iter(served(p)), "tpu") for p in (False, True))
    assert full.devices == bare.devices and full.spans == bare.spans
    assert tr.breakdown(full, *full.window()) == tr.breakdown(bare, *bare.window())


def test_admit_wait_reads_the_programs_stamps():
    read = harness.metric_reader(BENCH, "admit_wait_p90_ms")
    trace = tr.from_planes(iter(served(True)), "tpu")
    # due 1.0: 0.01 late + 0.05 in the queue; due 2.0: 0.2; due 9.0, never
    # admitted: its age at the close (1.0); due 11.0 is after the window
    want = float(np.percentile([0.06, 0.2, 1.0], 90)) * 1e3
    assert read(record(trace, tracks())) == pytest.approx(want)
    assert read(record(trace, tracks(with_admit=False))) is None
    assert read(record(trace, [])) is None


def test_traced_run_reports_admission_wait_and_leaves_spans_off(tiny_root):
    from repro.serving import spans

    cell = harness.load_cell("tiny.open", root=tiny_root)
    r = harness.run(cell, 21, 2.0, True, time.perf_counter())
    assert r["correct"] and not spans.enabled()
    m = r["metrics"]
    # the engine stamps admission just before the wrapper stamps the
    # prefill's start, on another clock
    assert m["admit_wait_p90_ms"]["value"] == pytest.approx(m["queue_wait_p90_ms"]["value"], abs=5.0)
