"""Benchmark driver: one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig2,...]

Emits ``name,us_per_call,derived`` CSV rows (see benchmarks.common).
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_batch, bench_correctness, bench_dist,
                        bench_greedy, bench_kernel, bench_protein,
                        bench_rnbp, bench_router, bench_sla,
                        bench_tradeoff, bench_zoo)
from repro.compile_cache import use_compile_cache

SUITES = {
    "fig2_tradeoff": bench_tradeoff,
    "tableI-II_greedy": bench_greedy,
    "fig4_tableIII_rnbp": bench_rnbp,
    "fig5_correctness": bench_correctness,
    "protein": bench_protein,
    "kernel": bench_kernel,
    "batch": bench_batch,
    "dist": bench_dist,
    "router": bench_router,
    "sla": bench_sla,
    "zoo": bench_zoo,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated suite filter")
    ap.add_argument("--graphs", type=int, default=0,
                    help="override graphs per dataset")
    args = ap.parse_args()
    use_compile_cache()
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    for name, mod in SUITES.items():
        if only and not any(o in name for o in only):
            continue
        t0 = time.perf_counter()
        kwargs = {}
        if args.graphs:
            kwargs["n_graphs"] = args.graphs
        mod.run(full=args.full, **kwargs)
        print(f"# suite {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
