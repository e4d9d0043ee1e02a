"""One run of one cell: set-up, the measured window, the check, the result line.

Everything that belongs to one cell is found by name from ``BENCHMARK.json``:
the configuration's file (its ``file`` entry), the traffic mix
(``bench/traffic/<traffic>.json``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``, a function ``read(run)`` that returns a
number, or None where it finds nothing to read).  A new cell, configuration,
mix or metric is new files and entries; no code here changes.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench.lib import check, graphs, loops, ref, traffic as tmod


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json
    per_layer: list
    readers: dict  # per-layer metric name -> read(run)


def _load_reader(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload] if m["moves"] in names else [])]
    readers = {m["name"]: _load_reader(root / "bench" / "metrics" / f"{m['name']}.py")
               for m in per_layer}
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer, readers)


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader reads."""

    cell: str
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    graph_n: int
    graph_directed_edges: int
    answers: int  # queries completed in the window
    iterations: list  # relaxation rounds per completed query (solve loops)
    counters: dict  # the program's counters, their change over the window
    spans: list  # [(name, seconds)] of the program's own spans in the window
    trace: object  # bench.lib.trace.Summary, or None without --trace 1


def devices(chips: int):
    """The chips of this run; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips, JAX found {len(devs)}")
    return devs


def peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, keep_trace: Path | None = None) -> dict:
    """Makes one run; returns the result line as a dict.  ``keep_trace``
    names a file to copy the profiler's trace to (for the tests' data)."""
    import jax

    from bench.lib import systems
    from bench.lib.watch import CompileWatch

    phases = {"start": time.perf_counter() - t_start}
    cell = load_cell(root, workload)
    if require_chip:
        devs = devices(cell.chips)
    else:
        devs = jax.devices()
    phases["devices"] = time.perf_counter() - t_start
    watch = CompileWatch()
    cfg = cell.config
    query_seed = int(cfg["graph_seed"])
    src, dst, w, n, component = graphs.make_graph(cfg, seed)
    phases["graph"] = time.perf_counter() - t_start
    system = systems.build(cfg, src, dst, w, n)
    phases["prepare"] = time.perf_counter() - t_start
    loop = cell.traffic["loop"]
    if loop == "closed":
        loops.warm_closed(system, cell.traffic, component, query_seed)
    elif loop == "open":
        system.warmup()
    else:
        raise ValueError(f"unknown loop {loop!r}")
    phases["warm"] = time.perf_counter() - t_start
    compiles = watch.compiles
    # set-up's phases, each as seconds since the process started
    print(json.dumps({"setup": {"phases_s": phases, "compile_s": watch.seconds,
                                "compiles": compiles, "cache_hits": watch.cache_hits}}),
          flush=True)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        systems.record_program_spans(True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    if loop == "closed":
        win = loops.closed(system, cell.traffic, component, query_seed, seconds)
    else:
        win = loops.open_loop(system, cell.traffic, component, seed, query_seed, seconds)
    summary, spans = None, []
    if trace:
        jax.profiler.stop_trace()
        spans = systems.program_spans()
        systems.record_program_spans(False)
    if watch.compiles != compiles:
        raise RuntimeError(f"{watch.compiles - compiles} compilations inside the window")
    memory = peak_bytes(devs[:cell.chips])
    system.close()
    del system
    gc.collect()
    if trace:
        from bench.lib import trace as trmod

        pbs = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if keep_trace is not None:
            shutil.copyfile(pbs[-1], keep_trace)
        summary = trmod.reduce(trmod.load(pbs[-1]), chips=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_check = time.perf_counter()
    values = check_window(cell.traffic, win, src, dst, w, n, seed)
    print(f"reference check took {time.perf_counter() - t_check:.1f} s", file=sys.stderr)
    correct, checks = check.verdict(values)

    if trace:
        data = RunData(cell.name, cfg, cell.traffic, cell.chips, devs[0].device_kind, n,
                       2 * int(src.size), win.attempted - win.failed, win.iterations,
                       win.counters, spans, summary)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        got = dict(win.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(got[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary.busy_mean_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result


def check_window(traffic, win, src, dst, w, n, seed) -> dict:
    """The numbers that decide ``correct``, for what the window produced."""
    g = ref.Graph.from_edges(src, dst, w, n)
    pick = tmod.check_sample(len(win.answers), traffic, seed)
    answers = [win.answers[i] for i in pick]
    if traffic["loop"] == "closed":
        values = check.compare_solve(g, answers)
    else:
        values = check.compare_served(g, answers)
        values["unanswered"] = win.failed
    values["checked"] = len(answers)
    return values


def main(argv=None, root: Path | None = None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Runs one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root) if root is not None else Path(__file__).resolve().parents[2]
    t_start = time.perf_counter() if t_start is None else t_start
    try:
        result = run(root, args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


def compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout, for
    every executable however short its compile.  Called before JAX loads."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # the TPU runtime's logs go under the run's own TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
