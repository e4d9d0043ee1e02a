"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (``harness.run`` without the look for a
chip) at a small size on the CPU, with one fault planted in the program,
once for each fault a cell can have: a relaxation that returns its state
unchanged, half of a launch's lanes left out, the exchange between chips
left out, an answer altered where it is produced.  A sound run of each
loop comes out correct.  The control (the reference in bfloat16 in the
program's place) comes out not correct.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench.lib import harness
from conftest import SMALL_TRAFFIC, make_tiny_root

HERE = Path(__file__).resolve().parent


def run(root, cell, seconds=1.0):
    return harness.run(root, cell, 2**31 + 77, seconds, False, time.perf_counter(),
                       require_chip=False)


def unchanged_state(monkeypatch):
    import repro.core.voronoi as vmod

    monkeypatch.setattr(vmod, "relax_dense",
                        lambda g, st, active_cand=None: (st, jnp.zeros(g.n, bool)))


def altered_answer(monkeypatch):
    import repro.core.tree as tmod

    orig = tmod.extract_tree

    def plus_one(*a, **kw):
        t = orig(*a, **kw)
        return dataclasses.replace(t, total_distance=t.total_distance + 1.0)

    monkeypatch.setattr(tmod, "extract_tree", plus_one)


def half_batch(monkeypatch):
    """The first half of every launch's lanes solves the second half's seeds.
    The server pads a launch with copies of its first lane, so a launch of
    two requests or more answers some wrongly, however few it holds."""
    from repro.solver.backends import BatchBackend

    orig = BatchBackend.solve_raw

    def half(self, cfg, g, seeds, num_seeds, ell=None):
        seeds = jnp.asarray(seeds)
        h = seeds.shape[0] // 2
        return orig(self, cfg, g, seeds.at[:h].set(seeds[h:2 * h]), num_seeds, ell=ell)

    monkeypatch.setattr(BatchBackend, "solve_raw", half)


@pytest.mark.parametrize("cell", ["solve-s17-k16", "solve-s17-k10k", "serve-s14-fresh"])
def test_sound_run_is_correct(tiny_root, fresh_jit, cell):
    res = run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("cell,fault", [
    ("solve-s17-k16", unchanged_state),
    ("solve-s17-k16", altered_answer),
    ("serve-s14-fresh", unchanged_state),
    ("serve-s14-fresh", half_batch),
    ("serve-s14-fresh", altered_answer),
])
def test_fault_is_not_correct(tiny_root, fresh_jit, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = run(tiny_root, cell)
    assert not res["correct"], res["checks"]


MESH_FAULTS = ("exchange_left_out", "unchanged_state", "altered_answer")


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    """The mesh cell's runs on four virtual devices, in one process: sound,
    then with each of ``MESH_FAULTS`` planted; the result lines by fault."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(HERE / "mesh_fault.py"),
                          str(tmp_path_factory.mktemp("mesh")), *MESH_FAULTS],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    runs = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith('{"correct"')]
    return {r["fault"]: r for r in runs}


def test_mesh_exchange_left_out(mesh_runs):
    """On four virtual devices: the sound mesh run is correct, and with the
    all-gather of the per-round state replaced by the chip's own block it
    is not."""
    sound, broken = mesh_runs["sound"], mesh_runs["exchange_left_out"]
    assert sound["correct"] and sound["device"]["count"] == 4, sound["checks"]
    assert all(c["value"] == 0 for c in sound["checks"].values())
    assert not broken["correct"], broken["checks"]


@pytest.mark.parametrize("fault", ["unchanged_state", "altered_answer"])
def test_mesh_fault_is_not_correct(mesh_runs, fault):
    """A mesh relaxation round that returns its state unchanged, and a
    total altered where the engine's result is made, read not correct."""
    assert not mesh_runs[fault]["correct"], mesh_runs[fault]["checks"]


@pytest.mark.parametrize("cell", ["solve-s17-k16", "serve-s14-fresh"])
def test_control_is_not_correct(tmp_path, cell, capsys):
    """At scale 10: below it, trees can be light enough (under 256) for
    bfloat16 to add them exactly."""
    from bench import control

    root = make_tiny_root(tmp_path, scale=10, traffic=SMALL_TRAFFIC)
    assert control.main(["--workload", cell, "--seeds", "1,2,3", "--queries", "3"],
                        root=root) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3 and not any(x["correct"] for x in lines)
