#!/usr/bin/env python3
"""Finds the highest arrival rate an open-loop serving cell sustains.

    python bench/knee.py --workload serve-s14-fresh --rates 6,8,10,12,14 --seconds 20

One process: the cell's graph and server are set up once, then each rate
runs for ``--seconds`` through the cell's own open loop.  A rate is
sustained when the answers keep up with the arrivals: the latency of the
last third of the requests is no more than 1.5 times that of the first
third.  Prints one JSON line per rate.  The benchmark's own runs never run
it; the rate it finds goes into the traffic file as a number.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench.lib import graphs, harness, loops, traffic as tmod  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    harness.compile_cache(ROOT)
    from bench.lib import systems

    cell = harness.load_cell(ROOT, args.workload)
    harness.devices(cell.chips)
    src, dst, w, n, component = graphs.make_graph(cell.config, args.seed)
    server = systems.build(cell.config, src, dst, w, n)
    server.warmup()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_qps=rate)
        t0 = time.perf_counter()
        win = loops.open_loop(server, traffic, component, args.seed + i,
                              int(cell.config["graph_seed"]), args.seconds)
        lat = win.latencies
        third = max(1, len(lat) // 3)
        first, last = float(np.median(lat[:third])), float(np.median(lat[-third:]))
        print(json.dumps({"rate_qps": rate, **win.end_to_end, "requests": win.attempted,
                          "failed": win.failed, "latency_first_third_s": first,
                          "latency_last_third_s": last, "sustained": last <= 1.5 * first,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
