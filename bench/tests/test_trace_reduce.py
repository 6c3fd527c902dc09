"""The trace reduction on a hand-built trace."""

from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def planes():
    # one chip busy 10-30 and 50-60 (ops overlap inside the first run),
    # another platform's device, and host spans with arguments
    return iter([
        plane("/host:CPU", {"python": [
            ev("bench.window", 0, 100),
            ev("bench.step", 5, 40),
            ev("bench.decode", 8, 25, replica=0, active=3, ctx=30),
            ev("bench.step", 48, 40),
            ev("other", 1, 2)]}),
        plane("/device:TPU:0", {"XLA Modules": [ev("jit_fn", 10, 50)],
                                 "XLA Ops": [ev("fusion.1", 10, 15), ev("copy.2", 20, 10),
                                             ev("fusion.1", 50, 10)]}),
        plane("/device:GPU:0", {"XLA Ops": [ev("gemm", 0, 100)]}),
    ])


def test_picks_device_planes_by_platform_and_ops_line():
    t = tr.from_planes(planes(), "tpu")
    assert list(t.devices) == [0]
    assert [e[0] for e in t.devices[0]] == ["fusion.1", "copy.2", "fusion.1"]
    assert t.window() == (0.0, 100.0)
    (decode,) = t.spans_named("bench.decode")
    assert decode[3] == {"replica": 0, "active": 3, "ctx": 30}
    assert [s[0] for s in t.spans] == ["bench.window", "bench.step", "bench.decode", "bench.step"]


def test_union_busy_and_idle_share():
    t = tr.from_planes(planes(), "tpu")
    merged = tr.union(t.devices[0], *t.window())
    assert merged == [(10.0, 30.0), (50.0, 60.0)]
    busy = tr.Busy(merged)
    assert busy.total == 30.0
    assert 1 - busy.total / 100 == pytest.approx(0.7)
    assert busy.within(8, 33) == 20.0
    assert busy.within(25, 55) == 10.0
    assert busy.within(60, 100) == 0.0
    assert tr.union([("a", 5, 20)], 10, 15) == [(10, 15)]


def test_idle_gaps_are_labelled_with_the_open_span():
    t = tr.from_planes(planes(), "tpu")
    lo, hi = t.window()
    gaps = tr.idle_gaps(tr.union(t.devices[0], lo, hi), lo, hi)
    assert gaps == [(0.0, 10.0), (30.0, 50.0), (60.0, 100.0)]
    index = tr.SpanIndex(t.spans)
    assert index.label(9) == "bench.decode"       # innermost of step and decode
    assert index.label(40) == "bench.step"
    assert index.label(46) == "outside steps"     # the window span does not count
    assert index.label(95) == "outside steps"


def test_breakdown_lists_ops_and_idle_by_host_activity():
    t = tr.from_planes(planes(), "tpu")
    b = tr.breakdown(t, *t.window())
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "copy.2"]
    assert [t for _, t in b["device_ops"]] == pytest.approx([25e-9, 10e-9])
    # gap 0-10 at 5: step open; 30-50 at 40: step; 60-100 at 80: step 48-88
    ((what, idle),) = b["idle_gaps"]
    assert what == "device idle during bench.step"
    assert idle == pytest.approx(70e-9)
