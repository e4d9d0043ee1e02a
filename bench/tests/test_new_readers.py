"""The readers of the program's own spans and counters, on exact inputs."""

import statistics
from pathlib import Path

import pytest

from bench.lib import harness

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    return harness._load_reader(ROOT / "bench" / "metrics" / f"{name}.py")


def data(spans=()):
    return harness.RunData(cell="c", config={}, traffic={}, chips=1,
                           device_kind="TPU v5 lite", graph_n=8, graph_directed_edges=16,
                           answers=2, iterations=[], counters={}, spans=list(spans),
                           trace=None)


@pytest.fixture
def recorder():
    """The program's recorder on, as the harness turns it on for a traced
    window, and reset afterwards."""
    from repro import obs

    obs.reset()
    obs.enable(trace=True, metrics=False)
    yield obs
    obs.reset()


def test_relax_useful_pct(recorder):
    read = reader("relax_useful_pct")
    assert read(data()) is None  # nothing recorded
    recorder.add_counter("solve_totals[single/bucket]", 0.0,
                         {"iterations": 3, "messages": 30, "relaxations": 9, "scanned": 300})
    recorder.add_counter("solve_totals[single/bucket]", 1.0,
                         {"iterations": 5, "messages": 70, "relaxations": 9, "scanned": 700})
    # a sample without a scan count (telemetry off) does not count
    recorder.add_counter("solve_totals[single/bucket]", 2.0, {"messages": 10 ** 9})
    recorder.add_counter("convergence[single/bucket]", 2.0, {"messages": 5, "scanned": 5})
    recorder.disable()  # the harness reads after the window
    assert read(data()) == pytest.approx(1.8)  # (9 + 9) / (300 + 700)


@pytest.mark.parametrize("mode", ["bucket", "frontier"])
def test_relax_useful_pct_compares_schedules(recorder, mode):
    """On a unit-weight path each reached vertex improves once under any
    schedule, so bucket and frontier read the same useful work and differ
    only by their scans."""
    import numpy as np

    from repro.core import from_edges
    from repro.solver import SolverConfig, SteinerSolver

    n = 40
    src = np.arange(n - 1, dtype=np.int32)
    g = from_edges(src, src + 1, np.ones(n - 1, np.float32), n, pad_to=8)
    cfg = SolverConfig(backend="single", mode=mode, ell_width=4, frontier_size=8)
    out = SteinerSolver(cfg).prepare(g).solve(np.array([0, 23], np.int32))
    recorder.disable()
    assert out.telemetry.relaxations == n - 2
    want = 100.0 * (n - 2) / out.telemetry.scanned
    assert reader("relax_useful_pct")(data()) == pytest.approx(want)


def test_relax_useful_pct_without_the_recorder():
    from repro import obs

    obs.reset()
    assert reader("relax_useful_pct")(data()) is None


def test_serve_queue_wait_p95_ms():
    read = reader("serve_queue_wait_p95_ms")
    waits = [0.1 * i for i in range(1, 41)]
    spans = [("serve:queue_wait", w) for w in waits] + [("serve:solve", 9.0)]
    want = 1e3 * statistics.quantiles(waits, n=20, method="inclusive")[-1]
    assert read(data(spans)) == pytest.approx(want)
    assert read(data(spans)) == pytest.approx(3805.0)
    assert read(data([("serve:queue_wait", 1.0)])) is None
    assert read(data()) is None


def test_serve_flush_host_ms():
    read = reader("serve_flush_host_ms")
    spans = [("serve:flush", 0.5), ("serve:solve", 0.45), ("serve:assemble", 0.01),
             ("serve:flush", 0.02), ("serve:flush", 1.0), ("serve:solve", 0.4),
             ("serve:solve", 0.5), ("solve", 0.3)]
    # (1.52 s of flushes - 1.35 s of launches) / 3 flushes
    assert read(data(spans)) == pytest.approx(1e3 * 0.17 / 3)
    assert read(data([("serve:solve", 0.4)])) is None
