"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (charter d).

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig4,table1,kernels,roofline]
    PYTHONPATH=src python -m benchmarks.run --only fig4 --backend spmd

Scale knobs via env: REPRO_BENCH_SCALE / REPRO_BENCH_ROUNDS /
REPRO_BENCH_SEEDS (paper seeds: 0,1,42); REPRO_BENCH_BACKEND (or
--backend) picks the federated execution backend (sequential | spmd).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = ("kernels", "fig4", "table1", "fig3", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--backend", default=None,
                    choices=["sequential", "spmd"],
                    help="federated execution backend for fig3/fig4/table1")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else set(MODULES)
    if args.backend:
        from benchmarks import common
        common.BACKEND = args.backend

    print("name,us_per_call,derived")
    failures = 0
    t0 = time.time()
    for name in MODULES:
        if name not in only:
            continue
        try:
            if name == "kernels":
                from benchmarks import kernels_micro
                kernels_micro.run()
            elif name == "fig3":
                from benchmarks import fig3_accuracy
                fig3_accuracy.run()
            elif name == "fig4":
                from benchmarks import fig4_comm_comp
                fig4_comm_comp.run()
            elif name == "table1":
                from benchmarks import table1_overview
                table1_overview.run()
            elif name == "roofline":
                from benchmarks import roofline_table
                roofline_table.run()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name}_FAILED,0.0,exception")
    print(f"bench_total_wall,{(time.time()-t0)*1e6:.0f},"
          f"{failures}_module_failures")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
