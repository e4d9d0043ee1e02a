"""Every per-layer reader that was there before the stage reduction was
added returns what it returned then, on the two committed chip traces
(``data/solve_small``, ``data/serve_small``) and fixed program numbers."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench.lib import harness, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# reader -> value on each trace, as the readers computed them when the
# stage reduction was added (unchanged code since the benchmark began)
PINNED = {
    "solve_small": {
        "relax_rounds": 8.0,
        "solve_roofline": 0.0022225561537425097,
        "device_idle_pct.solve": 17.058098612885765,
        "device_idle_pct.serve": 17.058098612885765,
        "serve_pad_waste_pct": 37.5,
        "serve_launch_ms": 4.5,
        "collective_pct": None,
    },
    "serve_small": {
        "relax_rounds": 8.0,
        "solve_roofline": 0.002123135561761648,
        "device_idle_pct.solve": 81.02705040992247,
        "device_idle_pct.serve": 81.02705040992247,
        "serve_pad_waste_pct": 37.5,
        "serve_launch_ms": 4.5,
        "collective_pct": None,
    },
}


@pytest.fixture(scope="module", params=sorted(PINNED))
def traced(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / f"{request.param}.xplane.pb"
    with gzip.open(DATA / f"{request.param}.xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return request.param, trace.reduce(trace.load(path))


@pytest.mark.parametrize("metric", sorted(PINNED["solve_small"]))
def test_reader_returns_what_it_did(traced, metric):
    name, summary = traced
    run = harness.RunData(
        cell=name, config={}, traffic={}, chips=1, device_kind="TPU v5 lite",
        graph_n=1024, graph_directed_edges=32768, answers=8, iterations=[8, 9, 7],
        counters={"serve_lanes_run_total": 64, "serve_lanes_padded_total": 24},
        spans=[("serve:solve", 0.004), ("serve:solve", 0.005)], trace=summary)
    read = harness._load_reader(ROOT / "bench" / "metrics" / f"{metric}.py")
    want = PINNED[name][metric]
    got = read(run)
    assert got is None if want is None else got == pytest.approx(want, rel=1e-12)
