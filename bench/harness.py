"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to a cell is found by name:

- `BENCHMARK.json` (repo root): the cell's configuration, traffic mix,
  chips, and which metrics it reports;
- `bench/cells/<cell>.json`: serving geometry (slots, `max_len`, page
  size) and the output check;
- `bench/configs/<config>.json`: the program's arch id and overrides,
  the sizes as run (checked against the program's config), the source,
  and the two yardsticks the configuration is judged by, each a Python
  file under the benchmark's directory named by an optional key:
  `"reference"` (default `reference.py`) and `"cost"` (default
  `flops.py`).  A configuration of another architecture brings its own
  two files and names them; a name that does not resolve is an error;
- `bench/traffic/<mix>.json`: parameters of `bench.loadgen`;
- `bench/metrics/<metric>.py`: `read(record) -> float | None`, one per
  metric, end-to-end and per-layer alike.  A metric `<q>.<cells>` that
  has no file of its own is read by `<q>.py`, so a quantity split by the
  end-to-end metric it moves keeps one reader.

A reference module provides `served_gaps(model, w, prompt, served,
pad_to, control=False) -> np.ndarray`: for one greedy request, how far
the reference's logit of each served token lies below the reference's
best; with `control`, the same gap of the token that its own forward in
the precision just below the configuration's (fp8 e4m3 under bfloat16)
puts first instead; and `lower_gaps(model, w, tokens)`, the program that
`served_gaps` runs, lowered for weights and a padded token vector given
as arrays or `ShapeDtypeStruct`s, so that `bench/rehearse.py` can
compile it without a chip; `bench/reference.py`'s `gap_check(forward)`
builds both from a reference's `forward`.  It also provides `READS`, the
ends of the paths of every weight leaf it reads: a run whose weights
hold a leaf it does not read (a bias, a router) is refused before the
window (`check_reads`), not judged by a reference that leaves it out.
It computes in float32 at `precision=highest`, takes the weights
`bench/weights.py` makes from the seed and nothing else the program has
made, imports nothing of the program, computes in blocks (of positions,
layers or heads) where a whole-sequence pass would not fit beside the
timed sizes, and says in its docstring where it departs from the
published description of the architecture.

A cost module provides `decode_cost(model, active, ctx_total)` and
`prefill_cost(model, plen) -> (flops, bytes)`: the work a decode step of
`active` requests over `ctx_total` cached positions, or a prefill of
`plen` prompt tokens, needs by the algorithm, from the configuration's
`model` sizes, not what the program happens to do.  The peaks and the
least time they allow stay shared in `bench/flops.py`.

The window drives the program's own entry points, `ServingEngine.submit`
/ `step`.  Each new output token is stamped on the host clock when the
`step()` that made it returns.  Latencies count from the time a request
was due, so a long step shows as the wait it causes.  Every run records
when each prefill starts and how many KV positions each decode reads; in
a traced run the benchmark's spans (`bench.step`, `bench.prefill`,
`bench.decode`) also wrap those calls, over the whole window, and each
prefill or decode span ends when its logits are ready, which the
engine's next line waits for anyway.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from types import ModuleType

import numpy as np

from bench import flops, loadgen, trace_reduce, weights

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OK_REASONS = ("max_new_tokens", "length", "eos")
FAIL_REASONS = ("shed", "rejected", "capacity", "poison")


class HarnessError(Exception):
    pass


# -- cells ---------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict      # bench/configs/<config>.json
    geometry: dict    # bench/cells/<cell>.json
    mix: dict         # bench/traffic/<mix>.json
    e2e: list[str]
    per_layer: list[str]
    units: dict[str, str]
    bench_dir: Path
    reference: ModuleType   # the configuration's plain reference
    cost: ModuleType        # the configuration's cost model


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"missing file {path}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its files."""
    spec = _read_json(Path(root) / "BENCHMARK.json")
    bench_dir = Path(root) / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise HarnessError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(Path(root) / configs[w["config"]]["file"])

    def mine(entries):
        return [m["name"] for m in entries if name in m.get("workloads", [name])]

    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                geometry=_read_json(bench_dir / "cells" / f"{name}.json"),
                mix=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                e2e=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]),
                units=units, bench_dir=bench_dir,
                reference=yardstick(bench_dir, config.get("reference", "reference.py")),
                cost=yardstick(bench_dir, config.get("cost", "flops.py")))


@functools.lru_cache(maxsize=None)
def _module(path: Path) -> ModuleType:
    """The Python file at `path`, executed once per process, so that a
    program it jits compiles once."""
    mod_spec = importlib.util.spec_from_file_location("bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def yardstick(bench_dir: Path, name: str) -> ModuleType:
    """The module a configuration names as its reference or cost model: a
    Python file under the benchmark's directory."""
    base = Path(bench_dir).resolve()
    path = (base / name).resolve()
    if path.suffix != ".py" or base not in path.parents or not path.is_file():
        raise HarnessError(f"no Python file {name!r} under {base}")
    return _module(path)


def metric_reader(bench_dir: Path, metric: str):
    """`read` of `metrics/<metric>.py`, else of the file named by the part
    of `metric` before its first dot."""
    for stem in (metric, metric.split(".")[0]):
        path = Path(bench_dir) / "metrics" / f"{stem}.py"
        if path.is_file():
            break
    else:
        raise HarnessError(f"no reader for metric {metric!r} under {Path(bench_dir) / 'metrics'}")
    return _module(path.resolve()).read


def model_config(config: dict):
    """The program's ModelConfig for a configuration file; every size the
    file states must be what the program runs."""
    from repro import configs

    cfg = configs.get_config(config["arch"]).replace(**config.get("overrides", {}))
    for key, want in config["model"].items():
        got = getattr(cfg, key)
        if got != want:
            raise HarnessError(f"{config['arch']}: the program runs {key}={got!r}, the file states {want!r}")
    return cfg


# -- the system under test -----------------------------------------------------


def make_engine(cfg, params, geo: dict):
    from repro.serving.engine import ServingEngine

    return ServingEngine(cfg, params, max_batch=geo["slots"], max_len=geo["max_len"],
                         page_size=geo["page_size"], paged=True, compact=True, kv_quant=False,
                         queue_bound=0, guard_nan=True, shed_deadlines=False)


def has_work(eng) -> bool:
    return bool(eng.queue) or any(s is not None for s in eng.slots)


def warm_up(eng, geo: dict, mix: dict, vocab: int, rng) -> None:
    """Compile what the cell's traffic uses and nothing else: each prefill
    bucket its prompt lengths reach, and the decode at full width with
    every lane live."""
    from repro.serving.engine import Request

    lo, hi = loadgen.prompt_range(mix)
    bks = eng.buckets
    reach = [b for i, b in enumerate(bks) if b >= lo and (i == 0 or bks[i - 1] < hi)]
    lens = [min(b, hi) for b in reach] + [lo] * geo["slots"]
    for n, plen in enumerate(lens):
        eng.submit(Request(rid=-1 - n, prompt=rng.integers(0, vocab, plen).astype(np.int32),
                           max_new_tokens=2 if n < len(reach) else 3))
    while has_work(eng):
        eng.step()


# -- traffic -------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Track:
    """One request as its client sees it, on the drive clock."""

    req: object
    due: float
    submit: float = math.nan
    times: list = dataclasses.field(default_factory=list)   # one per output token
    prefill_at: float | None = None


class OpenLoop:
    """Requests due at fixed times, whatever the server does."""

    def __init__(self, items, ramp_s: float):
        from repro.serving.engine import Request

        due = -ramp_s
        self.tracks = []
        for it in items:
            self.tracks.append(Track(Request(rid=it.idx, prompt=it.prompt, max_new_tokens=it.max_new), due))
            due += it.gap
        self.i = 0

    def poll(self, t: float) -> list[Track]:
        j = self.i
        while j < len(self.tracks) and self.tracks[j].due <= t:
            j += 1
        out, self.i = self.tracks[self.i:j], j
        return out

    def next_due(self) -> float:
        return self.tracks[self.i].due if self.i < len(self.tracks) else math.inf


def drive(eng, source: OpenLoop, clock, close_after: float, on_open) -> tuple[float, float]:
    """Offer the traffic and step the engine until the window closes.
    `clock()` reads seconds from the nominal window start (negative during
    the ramp).  `on_open()` runs once at the window's start.  Returns the
    window (open, close) on that clock."""
    live: list[Track] = []
    t_open, t_close = None, math.inf
    while True:
        t = clock()
        if t_open is None and t >= 0:
            on_open()
            t_open = clock()
            t_close = t_open + close_after
            t = t_open
        if t >= t_close:
            break
        for tr in source.poll(t):
            tr.submit = t
            eng.submit(tr.req)
            live.append(tr)
        if has_work(eng):
            eng.step()
            t = clock()
            for tr in live:
                n = len(tr.req.out_tokens)
                if n > len(tr.times):
                    tr.times.extend([t] * (n - len(tr.times)))
            live = [tr for tr in live if not tr.req.done]
        else:
            nxt = min(source.next_due(), t_close if t_open is not None else 0.0)
            if nxt > t:
                time.sleep(min(nxt - t, 0.005))
    return t_open, t_close


# -- host records and spans --------------------------------------------------------


def instrument(eng, clock, calls: dict, annotate: bool) -> None:
    """Wrap the calls into the engine's decode state: record each
    prefill's start (`calls["prefill_at"]`, by prompt) and each decode's
    KV positions read (`calls["decode_ctx"]`).  With `annotate`, also
    wrap them and the engine's step in `bench.*` trace annotations."""
    from jax.profiler import TraceAnnotation

    st = eng.state
    inner_p, inner_d, inner_step = st.prefill, st.decode, eng.step

    def prefill(fn, params, b, seq, frames=None):
        calls["prefill_at"][id(seq)] = clock()
        if not annotate:
            return inner_p(fn, params, b, seq, frames)
        with TraceAnnotation("bench.prefill", tokens=len(seq)):
            last = inner_p(fn, params, b, seq, frames)
            last.block_until_ready()
        return last

    def decode(fn, params, next_token, active):
        ctx = int(sum(int(st.pool.index[b]) + 1 for b in active))
        calls["decode_ctx"].append(ctx)
        if not annotate:
            return inner_d(fn, params, next_token, active)
        with TraceAnnotation("bench.decode", active=len(active), ctx=ctx):
            logits, lane = inner_d(fn, params, next_token, active)
            logits.block_until_ready()
        return logits, lane

    def step():
        with TraceAnnotation("bench.step"):
            inner_step()

    st.prefill, st.decode = prefill, decode
    if annotate:
        eng.step = step


# -- what the metric readers see ---------------------------------------------------


@dataclasses.dataclass
class Record:
    """A finished window, as the metric readers see it.  Times on the
    drive clock are seconds; trace times are nanoseconds."""

    model: dict
    cost: ModuleType    # the configuration's cost model
    tracks: list[Track]
    lo: float
    hi: float
    setup_s: float
    chip: int = 0
    peak: dict | None = None
    trace: trace_reduce.Trace | None = None
    _busy: trace_reduce.Busy | None = None

    @property
    def window_s(self) -> float:
        if self.trace is not None:
            a, b = self.trace.window()
            return (b - a) * 1e-9
        return self.hi - self.lo

    def due_in_window(self) -> list[Track]:
        return [tr for tr in self.tracks if self.lo <= tr.due < self.hi]

    def failed(self, tr: Track) -> bool:
        return tr.req.done and tr.req.finish_reason in FAIL_REASONS

    def ttft_s(self) -> list[float]:
        out = []
        for tr in self.due_in_window():
            if self.failed(tr) and not tr.times:
                out.append(self.hi - self.lo)
            elif tr.times and tr.times[0] <= self.hi:
                out.append(tr.times[0] - tr.due)
            else:
                out.append(self.hi - tr.due)
        return out

    def token_gaps_s(self) -> list[float]:
        out = []
        for tr in self.tracks:
            ts = tr.times
            out.extend(b - a for a, b in zip(ts, ts[1:]) if self.lo <= b <= self.hi)
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for tr in self.tracks for t in tr.times if self.lo <= t < self.hi)

    def gaps_by_live(self) -> dict[int, tuple[int, float]]:
        """How many token gaps end in a step that served k requests, and
        their median (ms), by k: what a tail of the gaps is made of."""
        served = collections.Counter(t for tr in self.tracks for t in tr.times)
        by_k = collections.defaultdict(list)
        for tr in self.tracks:
            for a, b in zip(tr.times, tr.times[1:]):
                if self.lo <= b <= self.hi:
                    by_k[served[b]].append(b - a)
        return {k: (len(v), float(np.median(v)) * 1e3) for k, v in sorted(by_k.items())}

    # trace-derived
    def busy(self) -> trace_reduce.Busy:
        if self._busy is None:
            a, b = self.trace.window()
            self._busy = trace_reduce.Busy(trace_reduce.union(self.trace.devices.get(self.chip, []), a, b))
        return self._busy

    def spans(self, name: str) -> list[tuple[str, float, float, dict]]:
        """Spans `bench.<name>` that start inside the traced window."""
        a, b = self.trace.window()
        return [s for s in self.trace.spans_named("bench." + name) if a <= s[1] < b]

    def span_device_ns(self, span) -> float:
        """Device busy time inside a span."""
        return self.busy().within(span[1], span[2])

    def device_busy_fraction(self) -> float:
        a, b = self.trace.window()
        return self.busy().total / (b - a)

    def host_ms_per_step(self) -> float | None:
        """Mean time per `bench.step` span in which the chip is not busy
        (ms)."""
        steps = self.spans("step")
        if not steps:
            return None
        host = sum((e - s) - self.busy().within(s, e) for _, s, e, _ in steps)
        return host / len(steps) * 1e-6


def read_metrics(names: list[str], units: dict, bench_dir: Path, rec: Record) -> dict:
    out = {}
    for name in names:
        v = metric_reader(bench_dir, name)(rec)
        if v is not None:
            out[name] = {"value": float(v), "unit": units[name]}
    return out


# -- the output check ------------------------------------------------------------


def pick_checked(tracks: list[Track], n: int, seed: int) -> list[Track]:
    """A sample drawn from the seed of the finished requests: the longest,
    the rest at random."""
    done = [tr for tr in tracks if tr.req.done and tr.req.finish_reason in OK_REASONS
            and tr.req.out_tokens and tr.req.rid >= 0]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0x5EED])
    done.sort(key=lambda tr: tr.req.rid)
    longest = max(done, key=lambda tr: len(tr.req.prompt) + len(tr.req.out_tokens))
    rest = [tr for tr in done if tr is not longest]
    return [longest] + [rest[int(i)] for i in rng.permutation(len(rest))[: max(n - 1, 0)]]


def check_reads(reference: ModuleType, w) -> None:
    """Refuse weights with a leaf that `reference` does not read."""
    unread = [p for p in weights.leaf_paths(w)
              if not any(p == r or p.endswith("/" + r) for r in reference.READS)]
    if unread:
        raise HarnessError(f"the reference {Path(reference.__file__).name} reads no {unread}")


def widest_gaps(reference: ModuleType, model: dict, w, checked: list[Track], pad_to: int,
                control: bool = False) -> tuple[float, int]:
    """The widest gap over every served token of the checked requests by
    the configuration's `reference`, and how many tokens were checked."""
    widest, n = 0.0, 0
    for tr in checked:
        g = reference.served_gaps(model, w, tr.req.prompt, tr.req.out_tokens, pad_to, control)
        widest = max(widest, float(g.max()))
        n += len(g)
    return widest, n


def judge(chk: dict, gap: float, n_tok: int, compiles: int, nan_steps: int) -> dict:
    """Each number compared, with its limit and whether it holds."""
    return {
        "logit_gap": {"value": gap, "limit": chk["max_logit_gap"], "ok": gap <= chk["max_logit_gap"]},
        "checked_tokens": {"value": n_tok, "limit": chk["min_tokens"], "ok": n_tok >= chk["min_tokens"]},
        "compiles_in_window": {"value": compiles, "limit": 0, "ok": compiles == 0},
        "nan_steps": {"value": nan_steps, "limit": 0, "ok": nan_steps == 0},
    }


def _profile_options():
    """Device ops and the benchmark's annotations; no Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


# -- one run -----------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        control: bool = False) -> dict:
    """Set up, warm up, ramp, measure, check.  Returns the result line.
    With `control`, the fp8 control of the configuration's reference is
    also put in the program's place on the same sample and judged by the
    same checks (`result["control"]`; `bench/control.py`; the benchmark's
    own runs never do)."""
    import jax
    from tools.mozart_check.tracecheck import CompileMonitor

    geo, mix = cell.geometry, cell.mix
    cfg = model_config(cell.config)
    model = cell.config["model"]
    device = jax.devices()[0]
    params = weights.make(cfg, seed, device)
    check_reads(cell.reference, params)
    eng = make_engine(cfg, params, geo)
    warm_up(eng, geo, mix, cfg.vocab, np.random.default_rng([int(seed), 0xA11]))
    items = loadgen.generate(mix, seed, cfg.vocab, seconds, geo["max_len"])
    source = OpenLoop(items, mix["ramp_s"])
    calls = {"prefill_at": {}, "decode_ctx": []}
    instrument(eng, lambda: time.perf_counter() - t0, calls, annotate=trace)
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter() + mix["ramp_s"]
    monitor = CompileMonitor()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    opened = {}

    def on_open():
        opened["setup_s"] = time.perf_counter() - t_start
        opened["decodes"] = len(calls["decode_ctx"])
        monitor.__enter__()
        if trace:
            jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
            opened["span"] = jax.profiler.TraceAnnotation("bench.window")
            opened["span"].__enter__()

    lo, hi = drive(eng, source, lambda: time.perf_counter() - t0, seconds, on_open)
    if trace:
        opened["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    monitor.__exit__(None, None, None)
    gc.unfreeze()

    mem_peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    nan_steps = eng.stats["nan_steps"]
    for tr in source.tracks:
        tr.prefill_at = calls["prefill_at"].get(id(tr.req.prompt))
    rec = Record(model=model, cost=cell.cost, tracks=source.tracks, lo=lo, hi=hi,
                 setup_s=opened["setup_s"], chip=device.id)
    ctx = calls["decode_ctx"][opened["decodes"]:]
    capacity = geo["slots"] * geo["max_len"]
    info = {"window_s": hi - lo, "requests_done": sum(1 for tr in source.tracks if tr.req.done),
            "tokens_in_window": rec.tokens_in_window(),
            "kv_live_pct_mean": 100.0 * float(np.mean(ctx)) / capacity if ctx else None,
            "kv_live_pct_max": 100.0 * max(ctx) / capacity if ctx else None,
            "gaps_by_live": {str(k): [n, round(ms, 3)] for k, (n, ms) in rec.gaps_by_live().items()},
            "gaps_over_100ms": sum(1 for g in rec.token_gaps_s() if g > 0.1)}
    late = [tr.submit - tr.due for tr in rec.due_in_window() if not math.isnan(tr.submit)]
    if late:
        info["generator_late_p99_ms"] = float(np.percentile(late, 99) * 1e3)
    breakdown = None
    if trace:
        rec.trace = trace_reduce.load(trace_dir, device.platform)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.peak = flops.peaks(device.device_kind, cell.bench_dir / "peaks.json")
        metrics = read_metrics(cell.per_layer, cell.units, cell.bench_dir, rec)
        a, b = rec.trace.window()
        breakdown = trace_reduce.breakdown(rec.trace, a, b)
        device_extra = {"busy_s": rec.busy().total * 1e-9, "window_s": (b - a) * 1e-9}
    else:
        metrics = read_metrics(cell.e2e, cell.units, cell.bench_dir, rec)
        device_extra = {}

    # the check: the reference runs once the window is closed, the peak is
    # read and the program's state is freed
    chk = geo["check"]
    checked = pick_checked(source.tracks, int(chk["requests"]), seed)
    attempted = len(rec.due_in_window())
    failed = sum(1 for tr in rec.due_in_window() if rec.failed(tr))
    del eng, params, source
    gc.collect()
    w = weights.make(cfg, seed, device)
    gap, n_tok = widest_gaps(cell.reference, model, w, checked, geo["max_len"])
    checks = judge(chk, gap, n_tok, monitor.count, nan_steps)
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind, "count": cell.chips,
                   "memory_peak_bytes": mem_peak, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        c_gap, c_tok = widest_gaps(cell.reference, model, w, checked, geo["max_len"], control=True)
        c_checks = judge(chk, c_gap, c_tok, monitor.count, nan_steps)
        result["control"] = {"correct": all(c["ok"] for c in c_checks.values()), "checks": c_checks}
    del w
    result["info"] = info
    result["checks"] = checks
    return result
