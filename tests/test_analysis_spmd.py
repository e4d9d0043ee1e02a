"""repro.analysis.spmd: jaxpr-level SPMD/numeric analyses.

Three layers of assurance, mirroring the ast harness's both-directions
contract:

  * seeded-violation self-tests — one deliberately-broken program per
    rule MUST be caught (a blind gate is worse than none);
  * the real executables — every registered backend×mode combo MUST be
    clean modulo the committed baseline's spmd section;
  * runtime ground truth — a forced-8-device subprocess checks the
    uniformity verdicts against what a 2×4 mesh actually computes.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
BASELINE_PATH = os.path.join(REPO, "ANALYSIS_BASELINE.json")


# ----------------------------------------------------------------------------
# seeded violations: the gate must fire on every rule it claims to carry
# ----------------------------------------------------------------------------


def _seedable_rules():
    from repro.analysis.spmd.selftest import SEEDABLE_RULES

    return SEEDABLE_RULES


@pytest.mark.parametrize("rule", ["SP01", "SP02", "SP03", "NU01", "NU02", "DN01"])
def test_seeded_violation_is_caught(rule):
    from repro.analysis.spmd.selftest import seed_findings

    findings = seed_findings(rule)
    assert any(f.rule == rule for f in findings), (
        f"analyzer lost the {rule} bug class:\n"
        + "\n".join(f.render() for f in findings)
    )


def test_seedable_rules_cover_every_spmd_rule():
    from repro.analysis.suppress import SPMD_RULES

    assert set(_seedable_rules()) == set(SPMD_RULES)


# ----------------------------------------------------------------------------
# real executables: every combo traces and is clean modulo the baseline
# ----------------------------------------------------------------------------


def test_combos_come_from_the_live_registry():
    from repro.analysis.spmd import combos
    from repro.solver.config import BACKEND_MODES

    got = list(combos())
    assert got == [
        (b, m) for b in sorted(BACKEND_MODES) for m in BACKEND_MODES[b]
    ]
    assert len(got) >= 10  # the full matrix, not a sampled subset


def test_all_combos_clean_modulo_baseline():
    from repro.analysis import baseline
    from repro.analysis.spmd import analyze_all

    findings = analyze_all()
    with open(BASELINE_PATH, encoding="utf-8") as fh:
        entries = baseline.load_sections(fh.read()).get("spmd", [])
    new, _suppressed, _expired = baseline.split(findings, entries)
    assert new == [], "new spmd findings in the solver executables:\n" + (
        "\n".join(f.render() for f in new)
    )


def test_trace_for_analysis_returns_closed_jaxpr():
    from jax.extend import core as jax_core

    from repro.analysis.spmd.harness import trace_combo

    jaxpr = trace_combo("mesh1d", "dense")
    assert isinstance(jaxpr, jax_core.ClosedJaxpr)
    prims = set()

    from repro.analysis.spmd.jaxpr_tools import walk_eqns

    for eqn in walk_eqns(jaxpr.jaxpr):
        prims.add(eqn.primitive.name)
    # the real distributed program: shard_map with collectives inside
    assert "shard_map" in prims
    assert prims & {"psum", "pmin", "pmax", "all_gather"}


# ----------------------------------------------------------------------------
# suppressions apply to jaxpr provenance lines
# ----------------------------------------------------------------------------


def test_scoped_suppression_silences_spmd_finding(tmp_path):
    mod = tmp_path / "suppressed_spmd.py"
    mod.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import PartitionSpec as P\n"
        "\n"
        "def build(mesh):\n"
        "    def body(x):\n"
        "        return jnp.sum(x)  # jitlint: ignore[SP01]\n"
        "    return jax.jit(jax.shard_map(\n"
        "        body, mesh=mesh, in_specs=(P('data'),), out_specs=P(),\n"
        "        check_vma=False))\n",
        encoding="utf-8",
    )
    import importlib.util

    spec = importlib.util.spec_from_file_location("suppressed_spmd", mod)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)

    import jax.numpy as jnp

    from repro.analysis.spmd.harness import analyze_jaxpr
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    jaxpr = m.build(mesh).trace(jnp.arange(8.0)).jaxpr
    assert analyze_jaxpr(jaxpr, context="t") == []


# ----------------------------------------------------------------------------
# interval domain details worth pinning
# ----------------------------------------------------------------------------


def test_nu01_fires_only_on_proven_overflow():
    import jax

    from repro.analysis.spmd.harness import analyze_jaxpr

    def safe():
        # iota(1000) fits int16 comfortably — a proven NON-violation
        return jax.lax.iota("int32", 1000).astype("int16")

    def unknown(x):
        # unknown-range operand: must NOT fire (whitelist soundness)
        return x.astype("int16")

    import jax.numpy as jnp

    assert analyze_jaxpr(jax.jit(safe).trace().jaxpr, context="t") == []
    assert (
        analyze_jaxpr(
            jax.jit(unknown).trace(jnp.arange(4, dtype=jnp.int32)).jaxpr,
            context="t",
        )
        == []
    )


def test_nu01_proves_through_arithmetic():
    import jax

    from repro.analysis.spmd.harness import analyze_jaxpr

    def f():
        # [0, 99] * 1000 → [0, 99000]: provably past int16
        return (jax.lax.iota("int32", 100) * 1000).astype("int16")

    fs = analyze_jaxpr(jax.jit(f).trace().jaxpr, context="t")
    assert any(x.rule == "NU01" for x in fs)


def test_dn01_quiet_when_donated_buffer_is_dead():
    import functools

    import jax
    import jax.numpy as jnp

    from repro.analysis.spmd.harness import analyze_jaxpr

    @functools.partial(jax.jit, donate_argnums=0)
    def relabel(buf):
        return buf * 2.0

    def outer(x):
        return relabel(x) + 1.0  # x is never read again: legal donation

    jaxpr = jax.jit(outer).trace(jnp.ones(8, jnp.float32)).jaxpr
    assert analyze_jaxpr(jaxpr, context="t") == []


# ----------------------------------------------------------------------------
# CLI + runtime ground truth (subprocesses)
# ----------------------------------------------------------------------------


def _env():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def test_cli_seed_violation_exits_one_with_rule_id(tmp_path):
    artifact = tmp_path / "findings.json"
    run = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "spmd",
         "--seed-violation", "NU01", "--json", str(artifact)],
        capture_output=True, text=True, env=_env(), cwd=REPO,
    )
    assert run.returncode == 1, run.stdout + run.stderr
    assert "NU01" in run.stdout
    payload = json.loads(artifact.read_text())
    assert any(f["rule"] == "NU01" for f in payload["new"])


def test_cli_spmd_single_combo_clean_against_baseline():
    run = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "spmd",
         "--combo", "mesh1d/dense", "--baseline", BASELINE_PATH],
        capture_output=True, text=True, env=_env(), cwd=REPO,
    )
    assert run.returncode == 0, run.stdout + run.stderr


@pytest.mark.slow
def test_uniformity_verdicts_match_runtime_ground_truth():
    """2×4 forced-host run: flagged channel's rank rows disagree, clean
    channel's replicas are bit-identical and rows sum exactly to it."""
    script = os.path.join(REPO, "tests", "_spmd_ground_truth.py")
    env = _env()
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    run = subprocess.run(
        [sys.executable, script],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    assert "ok:" in run.stdout
