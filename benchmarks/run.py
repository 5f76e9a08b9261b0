"""Benchmark harness entry: one module per paper table/figure + system
benches. Prints ``name,us_per_call,derived`` CSV (assignment contract).

  PYTHONPATH=src python -m benchmarks.run            # fast (CPU-budget)
  PYTHONPATH=src python -m benchmarks.run --slow     # bigger reductions
  BENCH_FULL=1 ... --slow                            # paper-scale

Figures land in experiments/figs/, curves in experiments/bench/*.json,
roofline tables in experiments/roofline_*.md (from the dry-run artifacts).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slow", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. fig3_mnist)")
    args = ap.parse_args()
    fast = not args.slow
    use_compile_cache()

    from benchmarks import (ablation, comm_table, engine_bench,
                            fig2_clustering, fig3_mnist, fig5_cifar,
                            kernel_bench, roofline)
    modules = {
        "comm_table": comm_table,
        "fig2_clustering": fig2_clustering,
        "fig3_mnist": fig3_mnist,
        "fig5_cifar": fig5_cifar,
        "ablation": ablation,
        "engine_bench": engine_bench,
        "kernel_bench": kernel_bench,
        "roofline": roofline,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failed = False
    for name, mod in modules.items():
        try:
            for row in mod.main(fast=fast):
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}", flush=True)
        except Exception:
            failed = True
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc()
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
