#!/usr/bin/env python3
"""Runs one cell traced and reports device time by program stage and idle
time by host span.

    python bench/stage_report.py --workload solve-s17-k16 --seed <n> --seconds 51

From the root of a checkout, on the chip.  Makes the same run as
``bench/run.py --trace 1`` (and prints its result line), reads the
profiler trace (kept at ``--keep`` when given), and prints one more JSON
line: each stage's device seconds (``bench.lib.stages``), per
query and per relaxation round, the part of them that the fallback for
operations without metadata assigned, the stages' share of the busy time
without that fallback and with it, the device's idle seconds by the
innermost harness phase or program span, and the traced window's
end-to-end numbers (what tracing costs them).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness, loops, stages, systems, trace  # noqa: E402


def keep_end_to_end(into: dict) -> None:
    """Makes the harness's loops also leave each window's end-to-end
    numbers in ``into``: the harness prints none in a traced run, and
    they are what tracing costs."""
    for name in ("closed", "open_loop"):
        def wrapped(*a, _run=getattr(loops, name), **k):
            win = _run(*a, **k)
            into.update(win.end_to_end)
            return win
        setattr(loops, name, wrapped)


def report(planes, chips: int, answers: int, rounds_per_query: float | None,
           span_names) -> dict:
    """The stage numbers of one kept trace (``rounds_per_query``: the run's
    ``relax_rounds``)."""
    summary = trace.reduce(planes, chips=chips)
    secs = stages.stage_seconds(planes, chips=chips)
    fell = stages.fallback_seconds(planes, chips=chips)
    busy = sum(summary.busy_s)
    staged = sum(secs.get(s, 0.0) for s in stages.STAGES)
    out = {"stage_s": secs, "busy_s": busy, "window_s": summary.window_s,
           # the seconds in stage_s that the fallback assigned, and the ten
           # longest operations it assigned
           "fallback_s": fell,
           "fallback_ops": dict(list(stages.fallback_ops(planes, chips=chips).items())[:10]),
           # the four stages' share of busy time without the fallback, and with it
           "stage_share_of_busy": (staged - sum(fell.get(s, 0.0) for s in stages.STAGES)) / busy
           if busy else None,
           "stage_share_with_fallback": staged / busy if busy else None}
    if answers:
        out["g1_ms"] = 1e3 * secs.get("distance_graph", 0.0) / answers
        out["mst_extract_ms"] = 1e3 * (secs.get("mst", 0.0) + secs.get("extract", 0.0)) / answers
        if rounds_per_query:
            out["relax_round_ms"] = 1e3 * secs.get("voronoi", 0.0) / (rounds_per_query * answers)
    names = set(trace.HOST_PHASES) | set(span_names)
    out["idle_by_span"] = stages.idle_by_span(planes, names)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="where to keep the .xplane.pb")
    args = ap.parse_args()
    harness.compile_cache(ROOT)
    traced_e2e: dict = {}
    keep_end_to_end(traced_e2e)
    with tempfile.TemporaryDirectory() as tmp:
        keep = Path(args.keep or Path(tmp) / "trace.xplane.pb")
        try:
            res = harness.run(ROOT, args.workload, args.seed, args.seconds, True, T_START,
                              keep_trace=keep)
        except harness.Refused as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        print(json.dumps(res), flush=True)
        rounds = res["metrics"].get("relax_rounds", {}).get("value")
        names = {name for name, _ in systems.program_spans()}
        cell = harness.load_cell(ROOT, args.workload)
        out = report(stages.load(keep), cell.chips, res["attempted"] - res["failed"],
                     rounds, names)
    out["traced_end_to_end"] = traced_e2e
    print(json.dumps({"stages": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
