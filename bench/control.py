"""Readings for the output check's limit: the program and its control.

    python bench/control.py --workload <cell> --seconds 30 --seeds 1,2,3

Runs the cell once per seed in one process (the set-up compiles once),
each with its ramp and a `--seconds` window at the cell's own load, and
prints one JSON line per seed: the program's widest logit gap against
the plain reference and its verdict (`correct`), and the same for the
control (the reference in fp8, put in the program's place on the same
prompts and tokens, and judged by the same checks): `control_gap` and
`control_correct`, which must come out false.  The limit in
`bench/cells/<cell>.json` lies between the largest program reading and
the smallest control reading.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(cell, seed, args.seconds, False, time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "gap": r["checks"]["logit_gap"]["value"],
                          "control_gap": r["control"]["checks"]["logit_gap"]["value"],
                          "control_correct": r["control"]["correct"],
                          "checked_tokens": r["checks"]["checked_tokens"]["value"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                          "memory_peak_bytes": r["device"]["memory_peak_bytes"]}), flush=True)


if __name__ == "__main__":
    main()
