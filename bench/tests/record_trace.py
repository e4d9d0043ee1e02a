#!/usr/bin/env python3
"""Records the small chip traces that ``test_trace.py`` reads.

    python bench/tests/record_trace.py --workload solve-s17-k16 --out bench/tests/data/solve.xplane.pb

Runs the cell through the harness with ``--trace 1``, its graph cut to
``--scale`` so that the trace stays small, on the chip, and keeps the
trace.  Prints the run's result line and the trace's planes and lines.
"""

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).resolve().parent)]

from conftest import make_tiny_root  # noqa: E402

from bench.lib import harness, trace  # noqa: E402


def main() -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    harness.compile_cache(ROOT)
    with tempfile.TemporaryDirectory() as tmp:
        root = make_tiny_root(Path(tmp), args.scale)
        res = harness.run(root, args.workload, args.seed, args.seconds, True, t_start,
                          keep_trace=Path(args.out))
    print(json.dumps(res))
    for p in trace.load(args.out):
        print(p.name, {k: len(v) for k, v in p.lines.items()})
        for k, v in p.lines.items():
            if p.name.startswith("/device"):
                print("  ", k, [(e.name, e.dur) for e in v[:8]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
