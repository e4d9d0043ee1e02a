"""Observability: metrics/trace units, zero-cost-when-disabled solver
integration, uniform telemetry parity, serve spans, CLI flags.

The load-bearing guarantees:

  * enabling obs never changes trees, counters, or executable counts —
    per-round telemetry rides every fixpoint loop unconditionally, so
    the toggle is host-side only (asserted bit-for-bit below);
  * ``SolveOutput.telemetry`` is the one uniform counter surface across
    all backends (exact Python ints from int32 rows), and its per-round
    rows sum exactly to the aggregate counters.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core import from_edges
from repro.obs import (
    MetricsRegistry,
    Tracer,
    flight,
    parse_prometheus,
    regress,
    validate_chrome_trace,
)
from repro.obs.__main__ import main as obs_main
from repro.solver import SolverConfig, SteinerSolver, trace_count

from helpers import random_instance

ROOT = Path(__file__).resolve().parent.parent

MSG = obs.ROUND_CHANNELS.index("messages")
RELAX = obs.ROUND_CHANNELS.index("relaxations")
NCH = len(obs.ROUND_CHANNELS)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts and ends with obs disabled and empty."""
    obs.reset()
    yield
    obs.reset()


def _instance(trial):
    src, dst, w, n, seeds, edges = random_instance(trial)
    return from_edges(src, dst, w, n, pad_to=8), n, seeds


# ----------------------------------------------------------------------------
# metrics.py units
# ----------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "total requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    h = reg.histogram("lat_seconds")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 10.0
    assert h.percentile(50) == 2.5
    assert h.values() == (1.0, 2.0, 3.0, 4.0)


def test_counter_rejects_negative_increment():
    with pytest.raises(ValueError, match="only go up"):
        MetricsRegistry().counter("c_total").inc(-1)


def test_registry_get_or_create_and_kind_binding():
    reg = MetricsRegistry()
    assert reg.counter("x_total") is reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad-name")
    # label variants are distinct series under one name
    a = reg.counter("by_mode_total", labels={"mode": "a"})
    b = reg.counter("by_mode_total", labels={"mode": "b"})
    assert a is not b and len(reg.series("by_mode_total")) == 2


def test_prometheus_text_roundtrip():
    reg = MetricsRegistry()
    reg.counter("solves_total", "completed solves").inc(41)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("lat_seconds", labels={"path": "fresh"})
    h.observe(0.5)
    h.observe(1.5)
    samples = parse_prometheus(reg.prometheus_text())
    assert samples["solves_total"] == 41
    assert samples["queue_depth"] == 3
    assert samples['lat_seconds_count{path="fresh"}'] == 2
    assert samples['lat_seconds_sum{path="fresh"}'] == 2.0
    assert 'lat_seconds{path="fresh",quantile="0.5"}' in samples


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError, match="not a Prometheus sample"):
        parse_prometheus("this is { not a sample\n")
    with pytest.raises(ValueError, match="bad sample value"):
        parse_prometheus("x_total twelve\n")


# ----------------------------------------------------------------------------
# trace.py units
# ----------------------------------------------------------------------------


def test_tracer_span_export_and_validate(tmp_path):
    tr = Tracer()
    with tr.span("outer", mode="frontier"):
        t0 = tr.now()
    tr.add_span("retro", t0, tr.now(), round=0)
    tr.add_counter("convergence", tr.now(), {"frontier": 5, "share": 0.5})
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == 3
    conv = [e for e in doc["traceEvents"] if e["name"] == "convergence"][0]
    assert conv["args"] == {"frontier": 5, "share": 0.5}
    assert isinstance(conv["args"]["frontier"], int)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "outer" in names and "retro" in names


def test_validate_rejects_bad_traces():
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace([{"ph": "Z", "ts": 0.0}])
    with pytest.raises(ValueError, match="not monotonic"):
        validate_chrome_trace(
            [{"ph": "i", "ts": 5.0}, {"ph": "i", "ts": 1.0}]
        )
    with pytest.raises(ValueError, match="unclosed B"):
        validate_chrome_trace([{"ph": "B", "ts": 0.0, "name": "x"}])
    with pytest.raises(ValueError, match="E without matching B"):
        validate_chrome_trace([{"ph": "E", "ts": 0.0}])


# ----------------------------------------------------------------------------
# obs module switch — everything is inert until enable()
# ----------------------------------------------------------------------------


def test_disabled_by_default_everything_noops(tmp_path):
    assert not obs.enabled() and not obs.tracing()
    assert obs.counter("x_total") is None
    assert obs.gauge("x") is None and obs.histogram("x_s") is None
    assert obs.span("a") is obs.span("b")  # shared no-op object
    with obs.span("never-recorded"):
        pass
    obs.add_span("retro", 0.0, 1.0)
    obs.emit_round_telemetry(np.ones((2, NCH)), 0.0, 1.0, label="x")
    obs.add_counter("x", 0.0, {"a": 1})
    assert obs.prometheus_text() == ""
    assert obs.export_chrome_trace(str(tmp_path / "t.json")) is False


def test_enable_disable_keeps_data():
    obs.enable()
    obs.counter("kept_total").inc(5)
    obs.disable()
    assert obs.counter("kept_total") is None  # no new recording
    assert "kept_total 5" in obs.registry().prometheus_text()
    obs.enable()  # idempotent re-enable keeps the registry
    assert obs.counter("kept_total").value == 5


# ----------------------------------------------------------------------------
# solver integration — enabling obs is invisible to the computation
# ----------------------------------------------------------------------------

OBS_SPECS = [
    ("single", "dense"),
    ("single", "bucket"),
    ("single", "frontier"),
    ("single", "pallas"),
    ("batch", "bucket"),
    ("mesh1d", "bucket"),
    ("mesh1d", "frontier"),
    ("mesh2d", "bucket"),
]


@pytest.mark.parametrize("backend,mode", OBS_SPECS)
def test_enable_is_bit_identical_and_never_retraces(backend, mode):
    g, n, seeds = _instance(1)
    cfg = SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1))
    handle = SteinerSolver(cfg).prepare(g)
    if backend == "batch":
        seeds = np.stack([seeds, np.roll(seeds, 1)])
    off = handle.solve(seeds)
    base = trace_count()
    obs.enable()
    on = handle.solve(seeds)
    assert trace_count() == base, "obs toggle must not build new executables"
    assert np.array_equal(
        np.asarray(off.total_distance), np.asarray(on.total_distance)
    )
    assert np.array_equal(np.asarray(off.num_edges), np.asarray(on.num_edges))
    assert on.telemetry.iterations == off.telemetry.iterations
    assert on.telemetry.messages == off.telemetry.messages
    assert on.telemetry.relaxations == off.telemetry.relaxations


@pytest.mark.parametrize(
    "backend,mode",
    [
        ("single", "bucket"),
        ("single", "frontier"),
        ("single", "pallas"),
        ("mesh1d", "bucket"),
        ("mesh1d", "frontier"),
        ("mesh2d", "bucket"),
    ],
)
def test_telemetry_matches_raw_counters(backend, mode):
    """SolveOutput.telemetry replaces digging through backend-native raw."""
    g, n, seeds = _instance(0)
    cfg = SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1))
    out = SteinerSolver(cfg).prepare(g).solve(seeds)
    t = out.telemetry
    assert isinstance(t.iterations, int)
    assert isinstance(t.messages, int) and isinstance(t.relaxations, int)
    if backend == "single":
        raw_it = out.raw.stats.iterations
        raw_msg, raw_rx = out.raw.stats.messages, out.raw.stats.relaxations
    else:
        raw_it = out.raw.iterations
        raw_msg, raw_rx = out.raw.messages, out.raw.relaxations
    assert t.iterations == int(raw_it)
    assert t.messages == int(round(float(raw_msg)))
    assert t.relaxations == int(round(float(raw_rx)))
    # per-round rows (ROUND_CHANNELS order) sum exactly to the aggregates
    assert t.per_round is not None and t.per_round.shape == (t.iterations, NCH)
    assert t.per_round.dtype == np.int32
    assert int(t.per_round[:, MSG].sum()) == t.messages
    assert int(t.per_round[:, RELAX].sum()) == t.relaxations
    # the scan is a static count per round, times the rounds
    assert t.scanned > 0 and t.scanned % t.iterations == 0


def test_batch_telemetry_aggregates_lanes():
    g, n, _ = _instance(0)
    rng = np.random.default_rng(7)
    lanes = np.stack(
        [rng.choice(n, size=5, replace=False) for _ in range(2)]
    ).astype(np.int32)
    out = (
        SteinerSolver(SolverConfig(backend="batch", mode="bucket"))
        .prepare(g)
        .solve(lanes)
    )
    singles = [
        SteinerSolver(SolverConfig(backend="single", mode="bucket"))
        .prepare(g)
        .solve(lane)
        for lane in lanes
    ]
    t = out.telemetry
    assert t.iterations == max(s.telemetry.iterations for s in singles)
    assert t.messages == sum(s.telemetry.messages for s in singles)
    assert t.relaxations == sum(s.telemetry.relaxations for s in singles)
    assert t.per_round.shape == (t.iterations, NCH)
    assert int(t.per_round[:, MSG].sum()) == t.messages
    assert t.scanned == sum(s.telemetry.scanned for s in singles)


def test_telemetry_rounds_spill_and_zero():
    g, n, seeds = _instance(2)
    full = (
        SteinerSolver(SolverConfig(backend="single", mode="bucket"))
        .prepare(g)
        .solve(seeds)
    )
    iters = full.telemetry.iterations
    assert iters > 3  # the grid instance needs many rounds
    # H smaller than the round count: buffer truncates, aggregates exact
    small = (
        SteinerSolver(
            SolverConfig(backend="single", mode="bucket", telemetry_rounds=3)
        )
        .prepare(g)
        .solve(seeds)
    )
    assert small.telemetry.iterations == iters
    assert small.telemetry.messages == full.telemetry.messages
    assert small.telemetry.relaxations == full.telemetry.relaxations
    assert small.telemetry.scanned == full.telemetry.scanned
    assert small.telemetry.per_round.shape == (3, NCH)
    assert np.array_equal(small.telemetry.per_round, full.telemetry.per_round[:3])
    # H=0: no buffer at all, identical trees and counters
    off = (
        SteinerSolver(
            SolverConfig(backend="single", mode="bucket", telemetry_rounds=0)
        )
        .prepare(g)
        .solve(seeds)
    )
    assert off.telemetry.per_round is None
    assert off.total_distance == full.total_distance
    assert off.telemetry.messages == full.telemetry.messages
    assert off.telemetry.scanned == full.telemetry.scanned


def test_solve_emits_spans_and_convergence_tracks(tmp_path):
    g, n, seeds = _instance(1)
    obs.enable()
    handle = SteinerSolver(
        SolverConfig(backend="single", mode="frontier")
    ).prepare(g)
    handle.solve(seeds)
    path = tmp_path / "trace.json"
    assert obs.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) > 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert "prepare" in names and "solve" in names
    assert "prepare:ell_build" in names
    assert "solve:dispatch" in names and "solve:fetch" in names
    # per-round telemetry is counter tracks only: no spans with made-up times
    assert not any(n.startswith("round[") for n in names)
    assert not any(
        "synthetic_timing" in e.get("args", {}) for e in doc["traceEvents"]
    )
    conv = [
        e for e in doc["traceEvents"]
        if e["name"] == "convergence[single/frontier]"
    ]
    assert conv and all(e["ph"] == "C" for e in conv)
    assert set(conv[0]["args"]) == set(obs.ROUND_CHANNELS)
    totals = [
        e for e in doc["traceEvents"]
        if e["name"] == "solve_totals[single/frontier]"
    ]
    assert len(totals) == 1
    assert totals[0]["args"]["messages"] == sum(e["args"]["messages"] for e in conv)
    samples = parse_prometheus(obs.prometheus_text())
    assert any(k.startswith("solver_messages_total") for k in samples)
    assert any(k.startswith("solver_solve_seconds_count") for k in samples)


# ----------------------------------------------------------------------------
# serve integration — registry-backed stats + per-query spans
# ----------------------------------------------------------------------------


def test_serve_stats_match_prometheus_dump():
    from repro.serve import ServeConfig, SteinerServer

    g, n, _ = _instance(0)
    srv = SteinerServer(
        g, ServeConfig(buckets=(8,), max_batch=4, cache_capacity=16)
    )
    rng = np.random.default_rng(0)
    q1 = rng.choice(n, size=4, replace=False).tolist()
    q2 = rng.choice(n, size=4, replace=False).tolist()
    srv.submit(q1)
    srv.submit(q2)
    srv.flush()
    srv.submit(q1)  # repeat → cache path
    srv.flush()
    st = srv.stats()
    samples = parse_prometheus(srv.prometheus_text())
    assert st["completed"] == 3
    assert samples["serve_queries_completed_total"] == st["completed"]
    assert samples["serve_cache_hits_total"] == st["cache_hits"]
    assert samples['serve_batches_total{bucket="8"}'] == sum(
        st["batches_per_bucket"].values()
    )
    assert samples["serve_lanes_run_total"] == st["lanes_run"]


def test_serve_emits_query_spans():
    from repro.serve import ServeConfig, SteinerServer

    g, n, _ = _instance(0)
    obs.enable()
    srv = SteinerServer(
        g, ServeConfig(buckets=(8,), max_batch=4, cache_capacity=16)
    )
    rng = np.random.default_rng(1)
    srv.submit(rng.choice(n, size=4, replace=False).tolist())
    srv.flush()
    names = {e["name"] for e in obs.tracer().events()}
    assert {
        "serve:flush",
        "serve:queue_wait",
        "serve:assemble",
        "serve:solve",
        "serve:stash",
    } <= names
    assert validate_chrome_trace(obs.tracer().chrome_trace()) > 0


# ----------------------------------------------------------------------------
# CLI surfaces — graphstore flags and the obs validator
# ----------------------------------------------------------------------------


def _run_graphstore(args):
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    return subprocess.run(
        [sys.executable, "-m", "repro.graphstore", *args],
        capture_output=True,
        text=True,
        env=env,
    )


def test_graphstore_cli_json_and_quiet(tmp_path):
    store = tmp_path / "g.gstore"
    r = _run_graphstore(
        ["--json", "build", str(store), "--source", "rmat",
         "--scale", "6", "--edge-factor", "4"]
    )
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)  # stdout is exactly one JSON document
    assert doc["cmd"] == "build" and doc["m_directed"] > 0
    assert "built" in r.stderr  # progress rides the logger on stderr

    r = _run_graphstore(
        ["--json", "--quiet", "partition", str(store), "--blocks", "2"]
    )
    assert r.returncode == 0 and r.stderr == ""
    doc = json.loads(r.stdout)
    assert doc["cmd"] == "partition" and doc["shards"] == 2
    assert doc["meta"]["scheme"] == "1d"

    r = _run_graphstore(["--json", "--quiet", "info", str(store)])
    assert r.returncode == 0 and r.stderr == ""
    doc = json.loads(r.stdout)
    assert doc["partition"]["scheme"] == "1d"
    assert doc["degree"]["max"] >= doc["degree"]["min"]


def test_graphstore_cli_trace_and_metrics(tmp_path):
    store = tmp_path / "g.gstore"
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.txt"
    r = _run_graphstore(
        ["--quiet", "--trace", str(trace), "--metrics", str(metrics),
         "build", str(store), "--source", "rmat",
         "--scale", "6", "--edge-factor", "4"]
    )
    assert r.returncode == 0, r.stderr
    doc = json.loads(trace.read_text())
    assert validate_chrome_trace(doc) > 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert "ingest:build_store" in names
    assert "ingest:pass1_degrees" in names and "ingest:chunk" in names
    samples = parse_prometheus(metrics.read_text())
    assert samples["graphstore_ingest_edges_total"] > 0


def test_obs_cli_validate(tmp_path):
    tr = Tracer()
    with tr.span("build"):
        pass
    trace = tmp_path / "t.json"
    tr.export_chrome(str(trace))
    reg = MetricsRegistry()
    reg.counter("x_total").inc(2)
    metrics = tmp_path / "m.txt"
    metrics.write_text(reg.prometheus_text())
    ok = obs_main(
        ["validate", str(trace), "--metrics", str(metrics),
         "--require-span", "build"]
    )
    assert ok == 0
    assert obs_main(["validate", str(trace), "--require-span", "nope"]) == 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"ph": "Z", "ts": 0.0}]))
    assert obs_main(["validate", str(bad)]) == 1
    metrics.write_text("not { prometheus\n")
    assert obs_main(["validate", str(trace), "--metrics", str(metrics)]) == 1


# ----------------------------------------------------------------------------
# exposition-format conformance — pathological label values round-trip
# ----------------------------------------------------------------------------


def test_prometheus_label_escaping_roundtrip():
    reg = MetricsRegistry()
    weird = 'a\\b"c\nd,}e'
    reg.counter(
        "w_total", "line one\nline two \\ backslash", {"path": weird}
    ).inc(3)
    reg.gauge("g", "plain", {"x": "comma,brace}"}).set(7)
    text = reg.prometheus_text()
    # HELP newline must be escaped or the dump is not line-parseable
    [help_w] = [ln for ln in text.split("\n") if ln.startswith("# HELP w_total")]
    assert "\\n" in help_w
    samples = parse_prometheus(text)
    [wkey] = [k for k in samples if k.startswith("w_total")]
    assert samples[wkey] == 3.0
    assert samples['g{x="comma,brace}"}'] == 7.0
    # canonical keys are stable under re-parsing
    assert parse_prometheus(text) == samples


# ----------------------------------------------------------------------------
# tracer hygiene — leaked-span flush + atomic export
# ----------------------------------------------------------------------------


def test_flush_open_spans_records_leaked():
    tr = Tracer()
    cm = tr.span("abandoned")
    cm.__enter__()
    assert tr.flush_open_spans() == ["abandoned"]
    evs = [e for e in tr.events() if e["name"] == "abandoned"]
    assert evs and evs[0]["args"]["leaked"] is True
    assert tr.flush_open_spans() == []  # idempotent


def test_tracer_atexit_flushes_leaked_spans():
    code = (
        "from repro.obs.trace import Tracer\n"
        "tr = Tracer()\n"
        "cm = tr.span('leaky_span')\n"
        "cm.__enter__()\n"
    )
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env
    )
    assert p.returncode == 0, p.stderr
    assert "flushed 1 span(s)" in p.stderr and "leaky_span" in p.stderr


def test_export_chrome_atomic(tmp_path):
    tr = Tracer()
    with tr.span("s"):
        pass
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    assert validate_chrome_trace(json.loads(path.read_text())) > 0
    # no temp litter: the write went through tmp + os.replace
    assert list(tmp_path.glob("*.tmp.*")) == []


# ----------------------------------------------------------------------------
# serve gauges — pad_waste and queue depth on the scrape endpoint
# ----------------------------------------------------------------------------


def test_serve_pad_waste_and_queue_depth_gauges():
    from repro.serve import ServeConfig, SteinerServer

    g, n, _ = _instance(0)
    srv = SteinerServer(
        g, ServeConfig(buckets=(8,), max_batch=4, cache_capacity=16)
    )
    rng = np.random.default_rng(2)
    srv.submit(rng.choice(n, size=4, replace=False).tolist())
    s = parse_prometheus(srv.prometheus_text())
    assert s["serve_queue_depth"] == 1.0
    srv.flush()
    st = srv.stats()
    s = parse_prometheus(srv.prometheus_text())
    assert s["serve_queue_depth"] == 0.0
    # 1 real lane in a 4-lane batch → 3/4 padding; gauge == stats() value
    assert st["pad_waste"] == 0.75
    assert s["serve_pad_waste"] == pytest.approx(st["pad_waste"])


# ----------------------------------------------------------------------------
# per-rank flight recorder — (1,1) mesh unit coverage (the 2×4 forced-host
# assertions live in tests/_dist_prog.py)
# ----------------------------------------------------------------------------


def test_per_rank_config_validation():
    with pytest.raises(ValueError, match="telemetry_per_rank"):
        SolverConfig(backend="single", telemetry_per_rank=True)
    with pytest.raises(ValueError, match="telemetry_per_rank"):
        SolverConfig(
            backend="mesh1d", telemetry_per_rank=True, telemetry_rounds=0
        )


@pytest.mark.parametrize(
    "backend,mode",
    [
        ("mesh1d", "dense"),
        ("mesh1d", "bucket"),
        ("mesh1d", "frontier"),
        ("mesh2d", "dense"),
        ("mesh2d", "bucket"),
    ],
)
def test_per_rank_flight_recorder_single_device(backend, mode):
    g, n, seeds = _instance(2)
    kw = dict(ell_width=8, frontier_size=32) if mode == "frontier" else {}
    base = (
        SteinerSolver(
            SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1), **kw)
        )
        .prepare(g)
        .solve(seeds)
    )
    assert base.telemetry.per_rank is None
    out = (
        SteinerSolver(
            SolverConfig(
                backend=backend, mode=mode, mesh_shape=(1, 1),
                telemetry_per_rank=True, **kw,
            )
        )
        .prepare(g)
        .solve(seeds)
    )
    pr = out.telemetry.per_rank
    assert pr is not None
    assert pr.shape == (base.telemetry.per_round.shape[0], 1, NCH)
    flight.check_consistency(pr, out.telemetry.per_round)
    # the knob is observability-only
    np.testing.assert_array_equal(
        out.telemetry.per_round, base.telemetry.per_round
    )
    assert out.total_distance == base.total_distance
    assert out.telemetry.messages == base.telemetry.messages


def test_per_rank_emits_rank_counter_tracks(tmp_path):
    g, n, seeds = _instance(1)
    obs.enable()
    out = (
        SteinerSolver(
            SolverConfig(
                backend="mesh1d", mode="frontier", mesh_shape=(1, 1),
                ell_width=8, frontier_size=32, telemetry_per_rank=True,
            )
        )
        .prepare(g)
        .solve(seeds)
    )
    assert out.telemetry.per_rank is not None
    path = tmp_path / "trace.json"
    assert obs.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) > 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert "rank[mesh1d/frontier/0]" in names
    tracks = [
        e for e in doc["traceEvents"]
        if e["name"] == "rank[mesh1d/frontier/0]"
    ]
    assert len(tracks) == out.telemetry.per_rank.shape[0]
    assert set(tracks[0]["args"]) == set(obs.ROUND_CHANNELS)


# ----------------------------------------------------------------------------
# flight.py analytics
# ----------------------------------------------------------------------------


def test_flight_imbalance_and_stragglers():
    per_rank = np.zeros((3, 4, NCH), np.int32)
    per_rank[0, :, MSG] = [4, 0, 0, 0]  # one rank does everything
    per_rank[1, :, MSG] = [1, 1, 1, 1]  # perfectly balanced
    # round 2: no activity at all → imbalance 1.0 by definition
    imb = flight.load_imbalance(per_rank)
    assert imb[0, MSG] == 4.0
    assert imb[1, MSG] == 1.0
    assert imb[2, MSG] == 1.0
    strag = flight.straggler_ranks(per_rank)
    # rank 0 carried the max in both active rounds; ties count everyone
    assert strag[0] == (0, 2)
    assert dict(strag) == {0: 2, 1: 1, 2: 1, 3: 1}
    rep = flight.analyze(per_rank, label="unit")
    assert rep.n_ranks == 4 and rep.rounds == 3
    assert rep.global_totals[MSG] == 8.0
    assert rep.peak_imbalance[MSG] == 4.0
    # mean over ACTIVE rounds only: (4.0 + 1.0) / 2
    assert rep.mean_imbalance[MSG] == pytest.approx(2.5)
    assert rep.message_skew == pytest.approx(5.0 / 2.0)


def test_flight_consistency_check():
    per_rank = np.arange(2 * 3 * NCH, dtype=np.int32).reshape(2, 3, NCH)
    per_round = per_rank.sum(axis=1)
    flight.check_consistency(per_rank, per_round)  # exact → no raise
    bad = per_round.copy()
    bad[1, MSG] += 1
    with pytest.raises(ValueError, match="round 1"):
        flight.check_consistency(per_rank, bad, label="unit")
    with pytest.raises(ValueError, match="per_rank must be"):
        flight.analyze(np.zeros((2, 3)))


def test_flight_dump_load_render(tmp_path):
    per_rank = np.ones((2, 2, NCH), np.int32)
    per_rank[1, 0, MSG] = 5
    path = tmp_path / "flight.json"
    flight.dump_flight(
        str(path), per_rank, label="t", per_round=per_rank.sum(axis=1),
        extra={"graph": "unit"},
    )
    doc = flight.load_flight(str(path))
    np.testing.assert_array_equal(doc["per_rank"], per_rank)
    assert doc["extra"] == {"graph": "unit"}
    rep = flight.analyze(doc["per_rank"], label=doc["label"])
    txt = flight.render_report(rep)
    assert "Flight report: t" in txt and "messages" in txt
    md = flight.render_report(rep, fmt="markdown")
    assert "| channel |" in md
    with pytest.raises(ValueError, match="fmt"):
        flight.render_report(rep, fmt="html")
    notflight = tmp_path / "x.json"
    notflight.write_text("{}")
    with pytest.raises(ValueError, match="not a flight file"):
        flight.load_flight(str(notflight))


def test_obs_cli_report(tmp_path, capsys):
    per_rank = np.ones((2, 2, NCH), np.int32)
    path = tmp_path / "flight.json"
    flight.dump_flight(
        str(path), per_rank, label="t", per_round=per_rank.sum(axis=1)
    )
    assert obs_main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "Flight report: t" in out and "message_skew" in out
    assert obs_main(["report", str(path), "--markdown"]) == 0
    assert "| channel |" in capsys.readouterr().out
    # a flight whose rank rows do NOT sum to the globals must fail
    flight.dump_flight(
        str(path), per_rank, label="t", per_round=per_rank.sum(axis=1) + 1
    )
    assert obs_main(["report", str(path)]) == 1


# ----------------------------------------------------------------------------
# regress.py — the perf gate itself
# ----------------------------------------------------------------------------


def _lo(value, metric="m_lo", mad_samples=None):
    samples = (value,) if mad_samples is None else tuple(mad_samples)
    return regress.MetricResult(metric, "ms", False, samples)


def _hi(value):
    return regress.MetricResult("m_hi", "qps", True, (value,))


def test_regress_compare_thresholds():
    base = {
        "m_lo": {"value": 100.0, "mad": 2.0},
        "m_hi": {"value": 50.0, "mad": 1.0},
    }
    # unknown metrics use the default 1.8 ratio:
    # lower-better limit = max(100·1.8, 100 + 5·2) = 180
    assert regress.compare([_lo(179.0)], base)[0].status == "ok"
    assert regress.compare([_lo(181.0)], base)[0].status == "regress"
    # MAD widens a tight ratio (noise awareness): slack 5·4 = 20 lifts
    # the 1.1-ratio limit from 110 to 120
    noisy = {"m_lo": {"value": 100.0, "mad": 4.0}}
    assert regress.compare([_lo(115.0)], noisy, max_ratio=1.1)[0].status == "ok"
    assert (
        regress.compare([_lo(125.0)], noisy, max_ratio=1.1)[0].status
        == "regress"
    )
    # ...but the slack is capped at 0.4·baseline: a hugely noisy
    # baseline cannot hide a genuine big regression
    wild = {"m_lo": {"value": 100.0, "mad": 1000.0}}
    assert regress.compare([_lo(141.0)], wild, max_ratio=1.1)[0].status == (
        "regress"
    )
    # higher-better mirror: limit = min(50/1.8, 50 − 5·1) = 27.78
    assert regress.compare([_hi(28.0)], base)[0].status == "ok"
    assert regress.compare([_hi(27.0)], base)[0].status == "regress"
    # missing baseline is reported, never a crash
    v = regress.compare(
        [regress.MetricResult("unknown", "ms", False, (1.0,))], base
    )[0]
    assert v.status == "missing" and v.baseline is None
    # render covers every verdict shape
    text = regress.render_verdicts(
        regress.compare([_lo(1.0), _hi(1.0)], base)
    )
    assert "m_lo" in text and "m_hi" in text


def test_regress_median_and_mad():
    r = _lo(0.0, mad_samples=(10.0, 11.0, 14.0))
    assert r.value == 11.0
    assert r.mad == 1.0  # median(|{10,11,14} − 11|) = median{1,0,3}


def test_regress_injection_is_time_derived_only(monkeypatch):
    res = [
        regress.MetricResult("t", "ms", False, (10.0,), time_derived=True),
        regress.MetricResult("q", "qps", True, (100.0,), time_derived=True),
        regress.MetricResult(
            "w", "messages", False, (500.0,), time_derived=False
        ),
    ]
    out = {r.metric: r for r in regress.apply_injection(res, 2.0)}
    assert out["t"].value == 20.0  # latency doubles
    assert out["q"].value == 50.0  # throughput halves
    assert out["w"].value == 500.0  # deterministic work untouched
    assert regress.apply_injection(res, 1.0) == res
    monkeypatch.setenv(regress.INJECT_ENV, "2.5")
    assert regress.injection_factor() == 2.5
    monkeypatch.setenv(regress.INJECT_ENV, "-1")
    with pytest.raises(ValueError):
        regress.injection_factor()


def test_regress_history_and_baseline_files(tmp_path):
    res = [_lo(10.0), _hi(100.0)]
    hist = tmp_path / "h.jsonl"
    assert regress.append_history(hist, res, quick=True, k=1) == 2
    assert regress.append_history(hist, res, quick=True, k=1) == 2
    rows = regress.load_history(hist)
    assert len(rows) == 4  # append-only
    assert rows[0]["metric"] == "m_lo" and rows[0]["value"] == 10.0
    assert "platform" in rows[0]["env"]
    base = tmp_path / "b.json"
    regress.write_baseline(base, res)
    bl = regress.load_baseline(base)
    assert bl["m_lo"]["value"] == 10.0
    assert bl["m_hi"]["higher_is_better"] is True
    assert list(tmp_path.glob("*.tmp.*")) == []  # atomic baseline write
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    with pytest.raises(ValueError, match="not a baseline"):
        regress.load_baseline(bad)


def test_bench_cli_gate(tmp_path, monkeypatch):
    def fake(k, quick):
        return [
            regress.MetricResult(
                "steiner_warm_ms_bucket", "ms", False, (10.0,) * k
            )
        ]

    monkeypatch.setattr(regress, "GROUPS", {"fake": fake})
    hist, base = tmp_path / "h.jsonl", tmp_path / "b.json"
    args = [
        "bench", "--only", "fake", "--k", "3",
        "--history", str(hist), "--baseline", str(base),
    ]
    # no baseline yet: warn-and-pass, unless --strict
    assert obs_main(args) == 0
    assert obs_main(args + ["--strict"]) == 1
    assert obs_main(args + ["--update-baseline"]) == 0
    assert regress.load_baseline(base)["steiner_warm_ms_bucket"]["value"] == 10.0
    # clean pass against its own baseline
    assert obs_main(args) == 0
    # unknown group is an error, not a silent no-op
    assert obs_main(["bench", "--only", "nope", "--history", str(hist),
                     "--baseline", str(base)]) == 1
    # injected 2× slowdown must fire the gate (policy ratio 1.8, mad 0)
    monkeypatch.setenv(regress.INJECT_ENV, "2.0")
    assert obs_main(args) == 1
    rows = regress.load_history(hist)
    assert len(rows) == 5 and rows[-1]["injected"] == 2.0


# ----------------------------------------------------------------------------
# stage scopes, profiler-clock spans, exact integer telemetry
# ----------------------------------------------------------------------------

STAGE_SCOPES = ("voronoi", "distance_graph", "mst", "extract")


@pytest.mark.parametrize(
    "backend,mode",
    [("single", "bucket"), ("single", "frontier"), ("batch", "bucket"),
     ("mesh1d", "bucket")],
)
def test_lowered_hlo_holds_stage_scopes(backend, mode):
    from repro.solver.backends import trace_for_analysis

    g, n, seeds = _instance(0)
    cfg = SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1))
    text = trace_for_analysis(cfg, g, seeds).lower().as_text(debug_info=True)
    for scope in STAGE_SCOPES:
        # a vmapped stage reads vmap(<scope>)/...
        assert re.search(rf"\b{scope}\)?/", text), scope
    if backend == "mesh1d":
        assert "voronoi/while/body/exchange/" in text


def test_enabled_span_is_a_profiler_annotation(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    obs.enable(trace=True, metrics=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("solve"):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    obs.disable()
    with obs.span("never-annotated"):
        pass
    pb = next(tmp_path.rglob("*.xplane.pb"))
    host = [
        e.name for p in ProfileData.from_file(str(pb)).planes
        if p.name.startswith("/host") for line in p.lines for e in line.events
    ]
    assert "solve" in host and "never-annotated" not in host
    assert [e["name"] for e in obs.tracer().events() if e["name"] == "solve"] == ["solve"]


def test_gc_while_tracing_records_a_span():
    import gc

    gc.collect()  # obs off: no callback, nothing recorded
    obs.enable(trace=True, metrics=False)
    gc.collect()
    obs.disable()
    gc.collect()
    spans = [e for e in obs.tracer().events() if e["name"] == "gc"]
    assert spans and spans[-1]["args"]["generation"] == 2
    assert all(e["args"]["generation"] in (0, 1, 2) for e in spans)
    assert obs.tracer() is not None and not any(
        cb.__module__ == "repro.obs" for cb in gc.callbacks
    )


def test_hist_write_is_exact_int32_with_a_saturating_spill():
    import jax.numpy as jnp

    from repro.core.voronoi import I32_MAX, UNREACHED, _hist_write, hist_init, sat_add

    hist = hist_init(2)
    assert hist.dtype == jnp.int32 and hist.shape == (3, NCH)
    big = jnp.full((NCH,), 2**24 + 1, jnp.int32)
    hist = _hist_write(hist, jnp.int32(0), big)
    assert int(hist[0, MSG]) == 2**24 + 1  # an f32 row would read 2**24
    # rounds >= H add up in the spill slot; unreached keeps the latest
    row = jnp.arange(1, NCH + 1, dtype=jnp.int32)
    hist = _hist_write(hist, jnp.int32(2), row)
    hist = _hist_write(hist, jnp.int32(5), row)
    spill = np.asarray(hist[2])
    assert spill[MSG] == 2 * (MSG + 1) and spill[UNREACHED] == UNREACHED + 1
    # ... and hold at 2**31 - 1 instead of wrapping
    huge = jnp.full((NCH,), I32_MAX - 1, jnp.int32)
    hist = _hist_write(hist, jnp.int32(3), huge)
    hist = _hist_write(hist, jnp.int32(4), huge)
    assert int(hist[2, MSG]) == I32_MAX
    assert int(sat_add(jnp.int32(I32_MAX - 3), jnp.int32(10))) == I32_MAX
    assert int(sat_add(jnp.int32(7), jnp.int32(10))) == 17


def test_scanned_channel_bucket_and_frontier():
    g, n, seeds = _instance(1)
    bucket = (
        SteinerSolver(SolverConfig(backend="single", mode="bucket"))
        .prepare(g)
        .solve(seeds)
    )
    # dense/bucket read the whole (padded) edge array every round
    assert bucket.telemetry.per_round.dtype == np.int32
    assert bucket.raw.stats.scan_per_round == g.src.shape[0]
    assert bucket.telemetry.scanned == g.src.shape[0] * bucket.telemetry.iterations
    cfg = SolverConfig(
        backend="single", mode="frontier", ell_width=4, frontier_size=8
    )
    handle = SteinerSolver(cfg).prepare(g)
    front = handle.solve(seeds)
    R, k = handle.artifact("ell").nbr.shape
    # frontier reads its K selected rows' k lanes every round
    assert front.raw.stats.scan_per_round == min(8, R) * k
    assert front.telemetry.scanned == min(8, R) * k * front.telemetry.iterations
    assert 0 < front.telemetry.relaxations <= front.telemetry.scanned


def test_segmin_scatters_counts_the_passes(tmp_path):
    """Bucket rounds take two segment-min passes (the packed (lab, src)
    key), frontier rounds three; the batch sums its lanes' rounds; the
    ``solve_totals[...]`` sample carries the count."""
    g, n, seeds = _instance(1)
    obs.enable()
    bucket = (
        SteinerSolver(SolverConfig(backend="single", mode="bucket"))
        .prepare(g)
        .solve(seeds)
    )
    t = bucket.telemetry
    assert bucket.raw.stats.segmin_passes == 2
    assert t.segmin_scatters == 2 * t.iterations > 0
    front = (
        SteinerSolver(SolverConfig(backend="single", mode="frontier",
                                   ell_width=4, frontier_size=8))
        .prepare(g)
        .solve(seeds)
    )
    assert front.telemetry.segmin_scatters == 3 * front.telemetry.iterations
    lanes = np.stack([seeds, seeds[::-1]]).astype(np.int32)
    batch = (
        SteinerSolver(SolverConfig(backend="batch", mode="bucket"))
        .prepare(g)
        .solve(lanes)
    )
    lane_rounds = np.asarray(batch.raw.stats.iterations)
    assert batch.telemetry.segmin_scatters == 2 * int(lane_rounds.sum())
    path = tmp_path / "trace.json"
    assert obs.export_chrome_trace(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    totals = {e["name"]: e["args"] for e in events
              if e["name"].startswith("solve_totals[")}
    assert totals["solve_totals[single/bucket]"]["segmin_scatters"] == 2 * t.iterations
    assert totals["solve_totals[single/frontier]"]["segmin_scatters"] == (
        front.telemetry.segmin_scatters)
    assert totals["solve_totals[batch/bucket]"]["segmin_scatters"] == (
        batch.telemetry.segmin_scatters)


def _path_graph(n):
    """A unit-weight path 0 - 1 - ... - n-1: every schedule first reaches a
    vertex from its nearest seed, so it improves each vertex exactly once."""
    src = np.arange(n - 1, dtype=np.int32)
    return from_edges(src, src + 1, np.ones(n - 1, np.float32), n, pad_to=8)


@pytest.mark.parametrize(
    "backend,mode",
    [("single", "dense"), ("single", "bucket"), ("single", "frontier"),
     ("single", "pallas"), ("mesh1d", "bucket"), ("mesh1d", "frontier"),
     ("mesh2d", "bucket")],
)
def test_relaxations_mean_the_same_in_every_schedule(backend, mode):
    """``relaxations / scanned`` (``relax_useful_pct``) compares schedules:
    on a path each reached vertex improves once under any schedule, so
    the numerator is the same and only the scan differs."""
    n = 40
    g = _path_graph(n)
    seeds = np.array([0, 23], np.int32)
    cfg = SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1),
                       ell_width=4, frontier_size=8)
    t = SteinerSolver(cfg).prepare(g).solve(seeds).telemetry
    assert t.relaxations == n - len(seeds)
    assert t.relaxations <= t.scanned and t.scanned % t.iterations == 0


def test_gc_while_the_tracer_holds_its_lock_does_not_deadlock():
    """A collection can start inside the tracer's own critical section (any
    allocation can trigger one); its ``gc`` span must still be recorded.
    Run in a child process, so that a deadlock fails the test instead of
    hanging the run."""
    prog = (
        "import gc\n"
        "from repro import obs\n"
        "obs.enable(trace=True, metrics=False)\n"
        "tr = obs.tracer()\n"
        "with tr._lock:\n"
        "    gc.collect()\n"
        "obs.disable()\n"
        "assert any(e['name'] == 'gc' for e in tr.events())\n"
        "print('ok')\n"
    )
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    try:
        r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                           text=True, env=env, timeout=60)
    except subprocess.TimeoutExpired:
        pytest.fail("the gc callback deadlocked on the tracer lock")
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr


def test_mesh_counts_saturate_instead_of_wrapping():
    """On a forced four-device host mesh: the devices' message counts sum
    exactly up to 2**31 - 1 and hold there; a mesh1d solve with
    local_steps > 1 keeps exact rows and its scan count on the host; and
    a partition whose round scans 2**32 edges still traces (the scan is a
    host int, not an int32 on the device)."""
    prog = r'''
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import obs
from repro.core import from_edges
from repro.core.dist_steiner import DistSteinerConfig, make_dist_steiner, sat_psum
from repro.core.voronoi import I32_MAX
from repro.solver import SolverConfig, SteinerSolver

mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
ax = ("data", "model")
f = jax.jit(jax.shard_map(lambda x: sat_psum(x[0], ax)[None], mesh=mesh,
                          in_specs=P(ax), out_specs=P(ax)))
def total(xs):
    return int(f(jnp.array(xs, jnp.int32))[0])
assert total([2**30, 2**30 - 1, 0, 0]) == I32_MAX  # fits exactly
assert total([2**30, 2**30, 0, 0]) == I32_MAX  # 2**31: held, not wrapped
assert total([I32_MAX] * 4) == I32_MAX
assert total([70000, 70001, 65535, 1]) == 205537

rng = np.random.default_rng(3)
n = 64
src = rng.integers(0, n, 400).astype(np.int32)
dst = rng.integers(0, n, 400).astype(np.int32)
w = rng.integers(1, 20, 400).astype(np.float32)
g = from_edges(src, dst, w, n, pad_to=8)
seeds = np.array([1, 17, 40], np.int32)
cfg = SolverConfig(backend="mesh1d", mode="bucket", mesh_shape=(2, 2), local_steps=4)
h = SteinerSolver(cfg).prepare(g)
t = h.solve(seeds).telemetry
part = h.artifact("part")
msg = obs.ROUND_CHANNELS.index("messages")
assert t.per_round.dtype == np.int32
assert int(t.per_round[:, msg].sum()) == t.messages > 0
assert t.scanned == part.eb * 4 * 4 * t.iterations

nb = 1024
dcfg = DistSteinerConfig(n=2 * nb, nb=nb, num_seeds=3, mode="bucket",
                         local_steps=2, telemetry_rounds=8)
edges = 4 * 2**29  # a round reads 2**31 edges, twice
spec = lambda dt: jax.ShapeDtypeStruct((edges,), dt)
make_dist_steiner(mesh, dcfg).lower(
    spec(jnp.int32), spec(jnp.int32), spec(jnp.float32),
    jax.ShapeDtypeStruct((3,), jnp.int32))
print("ok")
'''
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0 and r.stdout.strip().endswith("ok"), r.stderr
