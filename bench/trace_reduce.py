"""From a profiler trace to the numbers the per-layer metrics read.

`load` turns an `.xplane.pb` (read with `jax.profiler.ProfileData`) into
a `Trace`: the device operations of each chip and the benchmark's own
host spans (`bench.*` annotations, with their arguments), all on the
profiler's one clock.  The rest works on that plain form, so it can be
tested on a hand-built trace:

- `union`: the merged busy intervals of a chip inside a window;
- `Busy.within`: busy time inside a span;
- `idle_gaps`: the gaps between busy intervals;
- `SpanIndex.label`: what the host was doing at an instant (the
  innermost open benchmark span);
- `breakdown`: the device operations that took most time and the idle
  time by what the host was doing.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

SPAN_PREFIX = "bench."
OPS_LINES = ("XLA Ops", "XLA Modules")


@dataclasses.dataclass
class Trace:
    # chip index -> [(op name, start_ns, end_ns)]
    devices: dict[int, list[tuple[str, float, float]]]
    # [(span name, start_ns, end_ns, arguments)]
    spans: list[tuple[str, float, float, dict]]

    def spans_named(self, name: str) -> list[tuple[str, float, float, dict]]:
        return [s for s in self.spans if s[0] == name]

    def window(self) -> tuple[float, float]:
        """The traced window: the `bench.window` span."""
        (w,) = self.spans_named(SPAN_PREFIX + "window")
        return w[1], w[2]


def device_planes(planes, platform: str) -> dict[int, object]:
    """Device planes of `platform` ("tpu" -> "/device:TPU:<n>"), keyed by
    the device number in the plane's name."""
    pat = re.compile(r"^/device:%s:(\d+)$" % re.escape(platform.upper()))
    out = {}
    for p in planes:
        m = pat.match(p.name)
        if m:
            out[int(m.group(1))] = p
    return out


def op_name(hlo: str) -> str:
    """A short name for an op event, whose name may be its whole HLO
    text: `%fusion.12 = bf16[..] fusion(..)` -> `fusion.12 (fusion)`."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    m = re.search(r"[\s)}]([a-z][\w-]*)\(", rest)
    return head.lstrip("%") + (f" ({m.group(1)})" if m else "")


def _ops_events(plane) -> list[tuple[str, float, float]]:
    lines = {ln.name: ln for ln in plane.lines}
    for name in OPS_LINES:
        if name in lines:
            chosen = [lines[name]]
            break
    else:
        chosen = list(plane.lines)
    out = []
    for ln in chosen:
        for e in ln.events:
            if e.duration_ns > 0:
                out.append((op_name(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return out


def from_planes(planes, platform: str) -> Trace:
    planes = list(planes)
    devices = {k: _ops_events(p) for k, p in device_planes(planes, platform).items()}
    spans = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                                  {k: v for k, v in e.stats}))
    spans.sort(key=lambda s: s[1])
    return Trace(devices=devices, spans=spans)


def load(trace_dir: str, platform: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(str(files[-1])).planes, platform)


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals of `events` ((name, start, end) or (start, end))
    clipped to [lo, hi]."""
    iv = sorted((e[-2], e[-1]) for e in events)
    out: list[list[float]] = []
    for s, e in iv:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """A chip's merged busy intervals, with busy time inside any span in
    O(log n)."""

    def __init__(self, merged):
        self.iv = list(merged)
        self.starts = [s for s, _ in self.iv]
        self.cum = [0.0]
        for s, e in self.iv:
            self.cum.append(self.cum[-1] + (e - s))

    def until(self, t: float) -> float:
        j = bisect.bisect_right(self.starts, t) - 1
        if j < 0:
            return 0.0
        s, e = self.iv[j]
        return self.cum[j] + max(0.0, min(t, e) - s)

    def within(self, a: float, b: float) -> float:
        return self.until(b) - self.until(a) if b > a else 0.0

    @property
    def total(self) -> float:
        return self.cum[-1]


def idle_gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class SpanIndex:
    """What the host was doing at an instant: the innermost benchmark
    span open then (the window span does not count), or "outside steps"
    when none is."""

    def __init__(self, spans):
        self.spans = sorted((s for s in spans if s[0] != SPAN_PREFIX + "window"),
                            key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] - s[1] for s in self.spans), default=0.0)

    def label(self, t: float) -> str:
        best = None
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.starts[j] >= t - self.longest:
            name, s, e, _ = self.spans[j]
            if s <= t < e and (best is None or e - s < best[1]):
                best = (name, e - s)
            j -= 1
        return best[0] if best else "outside steps"


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Top device operations by time (seconds, summed over chips), and
    device idle time (seconds, summed over chips) by what the host was
    doing, each at most `top` entries."""
    op_time: dict[str, float] = {}
    idle_by: dict[str, float] = {}
    index = SpanIndex(trace.spans)
    for events in trace.devices.values():
        for name, s, e in events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
        for s, e in idle_gaps(union(events, lo, hi), lo, hi):
            what = "device idle during " + index.label((s + e) / 2)
            idle_by[what] = idle_by.get(what, 0.0) + (e - s)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[n, t * 1e-9] for n, t in idle]}
