"""Compiles for a described TPU v5e (no chip needed): what interpret mode
cannot show — Mosaic's layout rules and whether a program fits HBM.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  These compile; nothing runs.
"""

import os

import pytest

V5E_HBM = 15.75 * 2**30  # bytes XLA lets one v5e program use
GRAPH500_EDGE_FACTOR = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rmat_graph(scale, sharding):
    """Shapes of the padded symmetric COO of a Graph500 RMAT graph (upper
    bound: edge factor 16 plus the connecting path, both directions)."""
    import jax.numpy as jnp

    from repro.core.graph import Graph

    n = 1 << scale
    e = -(-2 * (GRAPH500_EDGE_FACTOR + 1) * n // 1024) * 1024
    return Graph(
        src=_sds((e,), jnp.int32, sharding),
        dst=_sds((e,), jnp.int32, sharding),
        w=_sds((e,), jnp.float32, sharding),
        n=n,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_minplus_kernel_compiles_for_v5e(one_chip, dtype):
    import jax.numpy as jnp

    from repro.kernels.minplus.minplus import minplus_call

    N = R = 1 << 20
    K = 32
    dt = jnp.dtype(dtype)
    compiled = minplus_call.lower(
        _sds((R, K), jnp.int32, one_chip),
        _sds((R, K), dt, one_chip),
        _sds((N,), dt, one_chip),
        _sds((N,), jnp.int32, one_chip),
        block_rows=256,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_solver_executable_holds_the_kernel(one_chip, monkeypatch):
    """The solver's pallas pipeline at RMAT scale 20 lowers with Mosaic
    (on a TPU the platform, not a knob, turns the interpreter off)."""
    import jax
    import jax.numpy as jnp

    from repro.core.graph import EllGraph
    from repro.solver import SolverConfig, get_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g = _rmat_graph(20, one_chip)
    rows = 2_000_128  # ~1.9 ELL rows per vertex at K = 32, padded to 256
    ell = EllGraph(
        nbr=_sds((rows, 32), jnp.int32, one_chip),
        wgt=_sds((rows, 32), jnp.float32, one_chip),
        row2v=_sds((rows,), jnp.int32, one_chip),
        n=g.n,
    )
    seeds = _sds((16,), jnp.int32, one_chip)
    cfg = SolverConfig(backend="single", mode="pallas")
    fn, _, kw = get_backend("single").dispatch(cfg, g, jnp.zeros(16, jnp.int32), 16, ell=ell)
    compiled = fn.lower(g, ell, seeds, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_single_bucket_scale22_fits_one_chip(one_chip):
    """The COO pipeline at Graph500 scale 22 compiles and fits one v5e."""
    import jax.numpy as jnp

    from repro.solver import backends

    g = _rmat_graph(22, one_chip)
    compiled = backends._exec_single_coo.lower(
        g,
        _sds((1024,), jnp.int32, one_chip),
        num_seeds=1024,
        mode="bucket",
        mst_algo="prim",
        delta=None,
        max_iters=None,
        telemetry_rounds=256,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
