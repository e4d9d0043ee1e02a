"""Runtime ground truth for the replica-uniformity verdicts (SP01).

Run as a subprocess (forced 8-host-device CPU) by test_analysis_spmd.py:

  * a shard_map program with one correctly psum'd channel and one
    per-rank channel exposed through a rank-axis out_spec → the analyzer
    must stay silent, and at runtime the psum'd channel's replicas are
    bit-identical while the per-rank rows differ AND sum bit-exactly to
    the global channel (the flight-recorder contract);
  * the same body returned through a REPLICATED out_spec without psum →
    the analyzer must flag SP01, and the runtime rows confirm the value
    genuinely varies per rank (the verdict is true, not a false alarm);
  * the real mesh1d executable traced on the same 2×4 mesh → clean, so
    the production telemetry channels' declarations match their values.

Exits 0 iff every static verdict matches the observed runtime behaviour.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.analysis.spmd.harness import analyze_jaxpr, tiny_graph  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main() -> int:
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))
    axes = ("data", "model")

    def body(x):
        local = jnp.sum(x)  # per-rank partial (int32 → sums are exact)
        glob = jax.lax.psum(local, axes)
        # both channels exposed per-rank: a legal, fully-declared program
        return glob.reshape(1), local.reshape(1)

    good = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(axes),),
            out_specs=(P(axes), P(axes)), check_vma=False,
        )
    )

    def bad_body(x):
        return jnp.sum(x)  # same partial, but claimed replicated below

    bad = jax.jit(
        jax.shard_map(
            bad_body, mesh=mesh, in_specs=(P(axes),), out_specs=P(),
            check_vma=False,
        )
    )

    x = jnp.arange(64, dtype=jnp.int32)

    # ---- static verdicts --------------------------------------------------
    good_findings = analyze_jaxpr(good.trace(x).jaxpr, context="ground/good")
    bad_findings = analyze_jaxpr(bad.trace(x).jaxpr, context="ground/bad")
    assert not [f for f in good_findings if f.rule == "SP01"], [
        f.render() for f in good_findings
    ]
    assert [f for f in bad_findings if f.rule == "SP01"], (
        "analyzer missed the unreduced replicated channel"
    )

    # ---- runtime ground truth on the 2×4 mesh -----------------------------
    glob_rows, local_rows = map(np.asarray, jax.device_get(good(x)))
    assert glob_rows.shape == (8,) and local_rows.shape == (8,)
    # "uniform" verdict: every replica of the psum'd channel is identical
    assert len(set(glob_rows.tolist())) == 1, glob_rows
    # "varying" verdict: the per-rank rows genuinely differ across ranks
    assert len(set(local_rows.tolist())) > 1, local_rows
    # flight-recorder contract: rank rows sum bit-exactly to the global
    assert int(local_rows.sum()) == int(glob_rows[0]) == int(np.arange(64).sum())

    # ---- the real executable on the same mesh is verdict-clean -----------
    from repro.analysis.spmd.harness import _combo_config
    from repro.solver.backends import trace_for_analysis

    cfg = _combo_config("mesh1d", "dense")
    cfg = type(cfg)(**{**cfg.__dict__, "mesh_shape": (2, 4),
                       "telemetry_per_rank": True})
    traced = trace_for_analysis(cfg, tiny_graph(), np.asarray([0, 5, 11], np.int32))
    real = analyze_jaxpr(traced.jaxpr, context="mesh1d/dense@2x4")
    assert real == [], [f.render() for f in real]

    print("ok: SP01 verdicts match the 2x4 forced-host runtime")
    return 0


if __name__ == "__main__":
    sys.exit(main())
