# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows; REPRO_BENCH_FAST=1 trims the DSE budgets for quick runs.
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "table1_internetwork",   # Table 1
    "fig2_hetero_memory",    # Figure 2
    "fig3_batch_scaling",    # Figure 3
    "table2_ttft",           # Table 2
    "table3_features",       # Table 3 (capability self-check)
    "fig7_pool_scaling",     # Figure 7
    "fig8_paradigms",        # Figure 8
    "fig9_cost_breakdown",   # Figure 9
    "fig10_llm_serving",     # Figure 10
    "fig11_specdec",         # Figure 11
    "fig12_av",              # Figure 12
    "roofline",              # §Roofline (from dry-run artifacts)
    "bench_codesign_search",  # engine speedup: cached/vectorized vs seed
    "bench_budget_scaling",  # search quality vs budget (monotone axes)
    "bench_batch_solve",     # generation-batched Layer-3 vs per-genome
    "bench_serving",         # compacted sub-batch decode vs PR-4 emulation
    "bench_cluster",         # multi-replica scale-out + int8 KV capacity
    "bench_chaos",           # goodput + token exactness under fault script
    "bench_specdec",         # live in-engine spec-decode vs target-only
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", nargs="*", default=None,
                   help="subset of benchmark modules to run")
    args = p.parse_args()
    use_compile_cache()
    mods = args.only or MODULES
    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception:  # noqa: BLE001 — report and continue
            failed.append(name)
            print(f"{name}.ERROR,0.0,{traceback.format_exc(limit=3)!r}")
    if failed:
        # Nonzero exit so CI sees benchmark breakage; the per-module
        # ERROR rows above carry the tracebacks.
        print(f"benchmarks failed: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
