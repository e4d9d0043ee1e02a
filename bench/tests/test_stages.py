"""Device time by stage and idle time by span (``bench.lib.stages``), on
chip traces and hand-made ones.

``data/solve_scoped`` and ``data/serve_scoped`` were recorded with
``record_trace.py`` (the solve and serve cells through the harness, graphs
cut to scale 10, one TPU v5e) from a program with its stage scopes and
profiler-clock spans; ``data/solve_small`` and ``data/serve_small`` from
one without.
"""

import gzip
import re
import shutil
from pathlib import Path

import pytest

from bench.lib import stages, trace
from bench.lib.stages import XPlane
from bench.lib.trace import Event

DATA = Path(__file__).resolve().parent / "data"
TRACES = ["solve_small", "serve_small", "solve_scoped", "serve_scoped"]
PROGRAM_SPANS = {"solve", "solve:dispatch", "solve:fetch", "serve:flush",
                 "serve:assemble", "serve:solve", "serve:stash", "gc"}


@pytest.fixture(scope="module")
def unpacked(tmp_path_factory):
    out = {}
    d = tmp_path_factory.mktemp("traces")
    for name in TRACES:
        out[name] = d / f"{name}.xplane.pb"
        with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, open(out[name], "wb") as dst:
            shutil.copyfileobj(src, dst)
    return out


@pytest.mark.parametrize("name", TRACES)
def test_reader_matches_profile_data(unpacked, name):
    mine = {p.name: p for p in stages.load(unpacked[name])}
    ref = {p.name: p for p in trace.load(unpacked[name])}
    assert mine.keys() == ref.keys()
    for pname, p in ref.items():
        assert mine[pname].lines.keys() == p.lines.keys()
        for line, evs in p.lines.items():
            got = mine[pname].lines[line]
            assert [e.name for e in got] == [e.name for e in evs]
            # ProfileData rounds to whole nanoseconds
            assert all(abs(a.start - b.start) < 2e-9 and abs(a.dur - b.dur) < 2e-9
                       for a, b in zip(got, evs))


@pytest.mark.parametrize("name", ["solve_scoped", "serve_scoped"])
def test_stages_cover_the_busy_time(unpacked, name):
    planes = stages.load(unpacked[name])
    secs = stages.stage_seconds(planes)
    s = trace.reduce(planes)
    # the same own times as the reduction's, named by stage
    total = sum(secs.values())
    assert total == pytest.approx(sum(s.ops.values()), rel=1e-9)
    assert set(stages.STAGES) <= set(secs)
    assert max(secs, key=secs.get) == "voronoi"
    # coverage counts only operations named by their own scope or loop
    fell = stages.fallback_seconds(planes)
    scoped = sum(secs.get(k, 0.0) - fell.get(k, 0.0) for k in stages.STAGES)
    if name == "solve_scoped":
        assert scoped >= 0.98 * total
        assert sum(fell.values()) < 1e-3 * total
    else:
        # the batch executable's vmapped G'1 scatter-mins are kCustom
        # fusions with no op_name (fusion.33-35 in its compiled HLO, one set
        # per bucket): the fallback gives them, and little else, to G'1
        g1 = {k: v for k, v in stages.fallback_ops(planes).items()
              if re.fullmatch(r"fusion\.3[345] [fs]32\[(520|8200)\] -> distance_graph", k)}
        assert len(g1) == 6
        assert sum(g1.values()) >= 0.99 * sum(fell.values())
        assert scoped + sum(g1.values()) >= 0.98 * total
    # the relaxation loop lands in voronoi
    dev = next(p for p in planes if trace.DEVICE_PLANE.match(p.name))
    evs = dev.lines[trace.OPS_LINE]
    loops = [(e.dur, st.name) for e, st in zip(evs, stages._staged(dev))
             if trace.op_name(e.name).startswith("while")]
    assert max(loops)[1] == "voronoi"
    assert {name for _, name in loops} <= set(stages.STAGES)


def test_unscoped_program_is_other(unpacked):
    secs = stages.stage_seconds(stages.load(unpacked["solve_small"]))
    assert set(secs) == {"other"}


@pytest.mark.parametrize("name", TRACES)
def test_idle_by_span_adds_up(unpacked, name):
    planes = stages.load(unpacked[name])
    s = trace.reduce(planes)
    idle = stages.idle_by_span(planes, set(trace.HOST_PHASES) | PROGRAM_SPANS)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s[0], abs=1e-9)
    assert set(idle) <= set(trace.HOST_PHASES) | PROGRAM_SPANS | {"other"}
    if name.endswith("scoped"):  # the program's spans are on the profiler's clock
        assert set(idle) & {"solve:dispatch", "solve:fetch", "serve:flush",
                            "serve:assemble", "serve:stash"}


def test_stage_of():
    assert stages.stage_of(None) is None
    assert stages.stage_of("") is None
    assert stages.stage_of("jit(_exec_single_coo)/jit(_voronoi_cells)/voronoi/while/body/"
                           "scatter-min:") == "voronoi"
    assert stages.stage_of("jit(_exec_batch)/vmap(distance_graph)/gather:") == "distance_graph"
    assert stages.stage_of("jit(f)/mst/while/body/extract/add:") == "mst"  # the first
    assert stages.stage_of("jit(_exec_single_coo)/transpose:") == "other"


def _plane(ops, tf_op, modules=((0.0, 100.0),)):
    return XPlane("/device:TPU:0", {
        trace.OPS_LINE: [Event(n, s, d) for n, s, d in ops],
        trace.MODULES_LINE: [Event("jit_f(1)", s, d) for s, d in modules]}, tf_op)


def test_hand_made_stages():
    host = XPlane("/host:CPU", {"python": [Event("window", 0.0, 100.0)]}, {})
    ops = [("%while.1 = (f32[8]) while(...)", 1.0, 10.0),
           ("%fusion.2 = f32[8]{0} fusion(...)", 2.0, 4.0),
           ("%fusion.3 = f32[8]{0} fusion(...)", 7.0, 3.0),
           ("%fusion.4 = f32[8]{0} fusion(...)", 12.0, 2.0),
           ("%fusion.5 = f32[8]{0} fusion(...)", 14.0, 1.0),  # no tf_op: follows fusion.4
           ("%fusion.6 = f32[8]{0} fusion(...)", 20.0, 1.0)]  # no tf_op, next module
    tf_op = {"%fusion.2 = f32[8]{0} fusion(...)": "jit(f)/voronoi/while/body/gather:",
             "%fusion.3 = f32[8]{0} fusion(...)": "jit(f)/voronoi/while/body/scatter:",
             "%fusion.4 = f32[8]{0} fusion(...)": "jit(f)/distance_graph/gather:"}
    dev = _plane(ops, tf_op, modules=((0.0, 16.0), (18.0, 5.0)))
    assert [e.name for e in stages._staged(dev)] == [
        "voronoi", "voronoi", "voronoi", "distance_graph", "distance_graph", "other"]
    assert stages._assign(dev)[1] == ["loop", "scope", "scope", "scope", "fallback", "fallback"]
    secs = stages.stage_seconds([host, dev])
    assert secs == pytest.approx({"voronoi": 10.0, "distance_graph": 3.0, "other": 1.0})
    assert stages.fallback_seconds([host, dev]) == pytest.approx(
        {"distance_graph": 1.0, "other": 1.0})
    assert stages.fallback_ops([host, dev]) == pytest.approx(
        {"fusion.5 f32[8] -> distance_graph": 1.0, "fusion.6 f32[8] -> other": 1.0})
    assert stages.stage_seconds([host, dev, dev]) == pytest.approx(
        {"voronoi": 20.0, "distance_graph": 6.0, "other": 2.0})


def test_hand_made_idle_by_span():
    host = XPlane("/host:CPU", {"python": [
        Event("window", 0.0, 10.0), Event("solve", 1.0, 6.0), Event("solve:fetch", 4.0, 2.0),
        Event("np.asarray(jax.Array)", 4.5, 1.0), Event("gc", 8.0, 0.5),
        Event("zero", 9.0, 0.0)]}, {})
    dev = _plane([("%fusion.1 = f32[8]{0} fusion(...)", 2.0, 2.5)], {})
    idle = stages.idle_by_span([host, dev], {"solve", "solve:fetch", "gc", "zero"})
    # idle: [0, 2) -> 1 other + 1 solve; [4.5, 10) -> 1.5 solve:fetch, 1 solve,
    # 0.5 gc, 2.5 other; an annotation outside names never counts
    assert idle == pytest.approx({"other": 3.5, "solve": 2.0, "solve:fetch": 1.5, "gc": 0.5})
    with pytest.raises(ValueError, match="window"):
        stages.idle_by_span([dev], {"solve"})


def test_stage_report_keeps_the_windows_end_to_end(tiny_root, fresh_jit, monkeypatch):
    import time

    from bench import stage_report
    from bench.lib import harness, loops

    for name in ("closed", "open_loop"):  # restored after the test
        monkeypatch.setattr(loops, name, getattr(loops, name))
    got: dict = {}
    stage_report.keep_end_to_end(got)
    res = harness.run(tiny_root, "solve-s17-k16", 2**31 + 5, 0.5, False, time.perf_counter(),
                      require_chip=False)
    assert res["correct"]
    assert got["tree_s"] == res["metrics"]["tree_s"]["value"]
