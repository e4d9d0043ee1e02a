"""The per-layer readers and the shape-based byte count, on exact inputs."""

from pathlib import Path

import pytest

from bench.lib import harness, peaks, trace, work

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    return harness._load_reader(ROOT / "bench" / "metrics" / f"{name}.py")


def data(**kw):
    base = dict(cell="c", config={}, traffic={}, chips=1, device_kind="TPU v5 lite",
                graph_n=131072, graph_directed_edges=4_186_000, answers=4, iterations=[],
                counters={}, spans=[], trace=None)
    base.update(kw)
    return harness.RunData(**base)


def summary(busy, window=10.0, coll=None):
    return trace.Summary(window, busy, coll or [0.0] * len(busy), {}, {}, [])


def test_solve_min_bytes_is_exact():
    # (src, dst, w) of every directed edge; (dist, lab, pred) of every vertex
    assert work.solve_min_bytes(4, 10) == 12 * 10 + 12 * 4
    assert work.solve_min_bytes(131072, 4_186_000) == 50_232_000 + 1_572_864


def test_solve_roofline():
    read = reader("solve_roofline")
    assert read(data()) is None  # not traced
    run = data(trace=summary([8.0]), answers=4)  # 2 s of device time per query
    want = 100 * work.solve_min_bytes(131072, 4_186_000) / (2.0 * 819e9)
    assert read(run) == pytest.approx(want, rel=1e-12)
    four = data(trace=summary([8.0] * 4), answers=4, chips=4)
    assert read(four) == pytest.approx(want / 4, rel=1e-12)
    with pytest.raises(KeyError, match="no peaks"):
        read(data(trace=summary([8.0]), device_kind="TPU v9"))


def test_idle_and_collectives():
    run = data(trace=summary([9.0, 8.0], coll=[0.9, 2.0]), chips=2)
    assert reader("device_idle_pct.solve")(run) == pytest.approx(15.0)
    assert reader("device_idle_pct.serve")(run) == pytest.approx(15.0)
    assert reader("collective_pct")(run) == pytest.approx((10.0 + 25.0) / 2)
    assert reader("collective_pct")(data(trace=summary([9.0]))) is None


def test_counters_and_spans():
    assert reader("relax_rounds")(data(iterations=[16, 17, 18])) == 17
    assert reader("relax_rounds")(data()) is None
    run = data(counters={"serve_lanes_run_total": 80, "serve_lanes_padded_total": 48})
    assert reader("serve_pad_waste_pct")(run) == 60.0
    assert reader("serve_pad_waste_pct")(data()) is None
    spans = [("serve:solve", 0.4), ("serve:assemble", 0.01), ("serve:solve", 0.5)]
    assert reader("serve_launch_ms")(data(spans=spans)) == pytest.approx(450.0)
    assert reader("serve_launch_ms")(data()) is None


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
