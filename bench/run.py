"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With `--trace 0` the last line of standard output reports the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics (read from a
profiler trace of a shorter window in a run of its own).  Both runs end
with the output check against the plain reference; the numbers compared
and their limits are the last lines of standard error and the last key
of the result line.  The run exits non-zero, and prints no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    cell = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']} {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
