#!/usr/bin/env python3
"""The control of a cell's check: the reference, computed in bfloat16, put in
the program's place.  It has to come out as not correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--queries 5]

For each seed it makes the cell's graph and the queries a run would send
(the first ``--queries`` of a closed loop, or the whole open schedule of
``run_seconds``), answers them with the bfloat16 reference, and compares
those answers with the exact reference by the cell's own numbers and
limits.  Prints one JSON line per seed.  Needs no chip; the benchmark's own
runs never run it.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT)]

import numpy as np  # noqa: E402

from bench.lib import check, graphs, harness, ref, traffic as tmod  # noqa: E402


def control_answers(cell, g, component, seed, queries, seconds):
    """What the bfloat16 reference answers, in the program's place, for the
    queries a run would check."""
    query_seed = int(cell.config["graph_seed"])
    if cell.traffic["loop"] == "closed":
        stream = tmod.ClosedStream(cell.traffic, component, query_seed)
        asked = [stream.next() for _ in range(queries)]
        out = []
        for i in tmod.check_sample(len(asked), cell.traffic, seed):
            t = ref.steiner(g, asked[i], ref.round_bf16)
            out.append(check.HostTree(
                seeds=asked[i], dist=t.dist, lab=t.lab, pred=t.pred, path_edge=t.path_edge,
                bridge_u=t.bridge_u, bridge_v=t.bridge_v, bridge_w=t.bridge_w,
                bridge_valid=np.ones(t.bridge_u.size, bool), total=t.total,
                num_edges=t.num_edges))
        return out
    _, asked = tmod.open_arrivals(cell.traffic, seconds, component, seed, query_seed)
    out = []
    for i in tmod.check_sample(len(asked), cell.traffic, seed):
        key = np.unique(asked[i])
        t = ref.steiner(g, key, ref.round_bf16)
        out.append((key, t.total, t.num_edges))
    return out


def main(argv=None, root: Path = ROOT) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=5)
    args = ap.parse_args(argv)
    cell = harness.load_cell(root, args.workload)
    seconds = json.loads((root / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        src, dst, w, n, component = graphs.make_graph(cell.config, seed)
        g = ref.Graph.from_edges(src, dst, w, n)
        answers = control_answers(cell, g, component, seed, args.queries, seconds)
        if cell.traffic["loop"] == "closed":
            values = check.compare_solve(g, answers)
        else:
            values = check.compare_served(g, answers)
            values["unanswered"] = 0
        values["checked"] = len(answers)
        correct, checks = check.verdict(values)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": correct,
                          "checks": checks, "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
