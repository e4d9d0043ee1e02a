#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Needs as many TPU chips as the cell asks for;
with fewer, or none, it exits with 2 and prints no result.  See PERF.md.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402

if __name__ == "__main__":
    harness.compile_cache(ROOT)
    sys.exit(harness.main(root=ROOT, t_start=T_START))
