"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV for CPU-sized instances.  Every
bench runs in this one process (a chip belongs to the first process that
touches it); fig3 uses however many of 1, 2, 4 devices the process has.

Usage: PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list: table1,fig3,...")
    args = ap.parse_args()
    from benchmarks import paper_tables as pt

    benches = {
        "table1": pt.bench_table1,
        "fig3": pt.bench_fig3,
        "fig4": pt.bench_fig4,
        "fig56": pt.bench_fig56,
        "fig7": pt.bench_fig7,
        "table5": pt.bench_table5,
        "table6": pt.bench_table6,
        "table7": pt.bench_table7,
        "frontier": pt.bench_frontier,
    }
    only = [x for x in args.only.split(",") if x]
    print("name,us_per_call,derived")
    failed = 0
    for key, fn in benches.items():
        if only and key not in only:
            continue
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:
            failed += 1
            print(f"{key},ERROR,{traceback.format_exc().splitlines()[-1]}",
                  flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
