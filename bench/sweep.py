"""Find the highest arrival rate an open-loop cell sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 40 --rates 1,1.5,2

One process, one engine: for each rate in turn it offers the cell's mix
at that rate (no ramp) for `--seconds`, then drains.  Each rate prints
one JSON line: offered and finished requests, the queue's depth over the
first and second half of the window and at its end, TTFT and token gap
percentiles, and output tokens per second.  A rate is sustained when the
queue does not grow over the window.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import harness, loadgen, weights
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    cfg = harness.model_config(cell.config)
    geo = cell.geometry
    eng = harness.make_engine(cfg, weights.make(cfg, args.seed), geo)
    harness.warm_up(eng, geo, cell.mix, cfg.vocab, np.random.default_rng(args.seed))
    depth: list[tuple[float, int]] = []
    inner = eng.step

    def step():
        inner()
        depth.append((time.perf_counter(), len(eng.queue)))

    eng.step = step
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate, ramp_s=0.0)
        items = loadgen.generate(mix, args.seed, cfg.vocab, args.seconds, geo["max_len"])
        src = harness.OpenLoop(items, 0.0)
        depth.clear()
        t0 = time.perf_counter()
        lo, hi = harness.drive(eng, src, lambda: time.perf_counter() - t0, args.seconds, lambda: None)
        rec = harness.Record(model=cell.config["model"], cost=cell.cost, tracks=src.tracks,
                             lo=lo, hi=hi, setup_s=0.0)
        half = t0 + lo + (hi - lo) / 2
        first = [d for t, d in depth if t < half]
        second = [d for t, d in depth if t >= half]
        ttft, gaps = rec.ttft_s(), rec.token_gaps_s()
        print(json.dumps({
            "rate_rps": rate,
            "offered": len(rec.due_in_window()),
            "finished": sum(1 for tr in rec.due_in_window() if tr.req.done),
            "queue_first_half": float(np.mean(first)) if first else 0.0,
            "queue_second_half": float(np.mean(second)) if second else 0.0,
            "queue_end": depth[-1][1] if depth else 0,
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "itl_p50_ms": float(np.percentile(gaps, 50)) * 1e3,
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
            "tokens_per_s": rec.tokens_in_window() / (hi - lo),
        }), flush=True)
        while harness.has_work(eng):
            inner()


if __name__ == "__main__":
    main()
