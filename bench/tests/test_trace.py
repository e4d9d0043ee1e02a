"""The trace reduction, on traces recorded on a TPU v5e and on hand-made ones.

``data/*.xplane.pb.gz`` were recorded with ``record_trace.py`` (the solve
and serve cells through the harness, graphs cut to scale 10, on one chip).
Each reduced number is checked against a plain recount of the same events.
"""

import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench.lib import trace
from bench.lib.trace import Event, Plane

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module", params=["solve_small", "serve_small"])
def recorded(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / f"{request.param}.xplane.pb"
    with gzip.open(DATA / f"{request.param}.xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return request.param, trace.load(path)


def _window(planes):
    w = max((e for p in planes if not trace.DEVICE_PLANE.match(p.name)
             for evs in p.lines.values() for e in evs if e.name == "window"),
            key=lambda e: e.dur)
    return w.start, w.start + w.dur


def _busy_by_hand(events, lo, hi, step=1e-7):
    """Busy seconds on a grid of ``step``: a recount with no interval merging."""
    grid = np.zeros(int(np.ceil((hi - lo) / step)) + 1, bool)
    for e in events:
        a, b = max(e.start, lo), min(e.start + e.dur, hi)
        if b > a:
            grid[int(round((a - lo) / step)):int(round((b - lo) / step))] = True
    return grid.sum() * step


def test_recorded_trace(recorded):
    name, planes = recorded
    s = trace.reduce(planes)
    lo, hi = _window(planes)
    dev = [p for p in planes if trace.DEVICE_PLANE.match(p.name)]
    assert len(dev) == s.devices == 1
    ops = dev[0].lines[trace.OPS_LINE]
    assert s.window_s == pytest.approx(hi - lo)
    assert 0 < s.busy_s[0] <= s.window_s
    assert s.busy_s[0] == pytest.approx(_busy_by_hand(ops, lo, hi), abs=2e-6 * len(ops))
    # own times cover the busy time; operations that overlap without
    # nesting (a few per cent here) count in each
    assert s.busy_s[0] <= sum(s.ops.values()) <= 1.05 * s.busy_s[0]
    assert s.collective_s == [0.0]  # one chip: nothing to exchange
    exe = "jit__exec_single_coo" if name == "solve_small" else "jit__exec_batch"
    assert set(s.modules) == {exe}
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and all(v > 0 for _, v in b["device_ops"])
    assert [v for _, v in b["device_ops"]] == sorted((v for _, v in b["device_ops"]), reverse=True)
    assert all(phase in trace.HOST_PHASES + ("other",) for phase, _ in b["idle_gaps"])
    gaps = [v for _, v in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= s.window_s - s.busy_s[0] + 1e-9
    if name == "serve_small":  # the queue runs dry between arrivals
        assert b["idle_gaps"][0][0] == "wait_arrival"


def _plane(name, ops=(), async_ops=(), modules=()):
    lines = {trace.OPS_LINE: [Event(*e) for e in ops],
             trace.ASYNC_LINE: [Event(*e) for e in async_ops],
             trace.MODULES_LINE: [Event(*e) for e in modules]}
    return Plane(name, lines)


def test_hand_made_four_chips():
    host = Plane("/host:CPU", {"python": [Event("window", 1.0, 10.0), Event("solve", 1.0, 6.0),
                                          Event("fetch", 7.0, 4.0)]})
    ops = [("%while.1 = (f32[8]) while(...)", 2.0, 4.0),
           ("%fusion.2 = f32[8]{0} fusion(...)", 2.5, 1.0),
           ("%all-gather.3 = f32[32]{0} all-gather(...)", 4.0, 1.0),
           ("%fusion.4 = f32[8]{0} fusion(...)", 0.0, 1.5)]  # starts before the window
    planes = [host] + [_plane(f"/device:TPU:{i}", ops, modules=[("jit_f(42)", 0.0, 6.0)])
                       for i in range(4)] + [_plane("/device:TPU:0 SparseCore")]
    s = trace.reduce(planes)
    assert s.devices == 4 and s.window_s == 10.0
    assert s.busy_s == [4.5] * 4  # [1, 1.5] and [2, 6]
    assert s.collective_s == [1.0] * 4
    assert s.ops == {"while.1": 8.0, "fusion.2 f32[8]": 4.0, "all-gather.3 f32[32]": 4.0,
                     "fusion.4 f32[8]": 2.0}
    assert s.modules == {"jit_f": 20.0}
    # idle: [1.5, 2] under solve, [6, 11] mostly under fetch
    assert s.gaps == [(5.0, "fetch"), (0.5, "solve")]
    assert s.breakdown()["device_ops"][0] == ["while.1", 2.0]


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="window"):
        trace.reduce([_plane("/device:TPU:0")])
    with pytest.raises(ValueError, match="device"):
        trace.reduce([Plane("/host:CPU", {"python": [Event("window", 0.0, 1.0)]})])
