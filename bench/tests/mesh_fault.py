"""Helper of ``test_faults.py``: runs of the mesh cell on four virtual CPU
devices, one sound and one with each named fault planted in the program,
one result line each, tagged ``fault``.  Run with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``:

    python mesh_fault.py <tmp dir> [fault ...]
"""

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent))
from conftest import make_tiny_root  # noqa: E402

from bench.lib import harness  # noqa: E402


def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
    """An all-gather over four chips that returns this chip's block four times."""
    parts = [x] * 4
    return jnp.concatenate(parts, axis=axis) if tiled else jnp.stack(parts, axis=axis)


class _NoCandidates:
    """``jax`` as the mesh engine sees it, with every segment-min of a
    relaxation round returning its fill: no vertex improves, so each round
    returns its state unchanged."""

    class ops:
        def __getattr__(self, name):
            return getattr(jax.ops, name)

        @staticmethod
        def segment_min(data, segment_ids, num_segments, **kw):
            fill = jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
                else jnp.iinfo(data.dtype).max
            return jnp.full((num_segments,), fill, data.dtype)

    ops = ops()

    def __getattr__(self, name):
        return getattr(jax, name)


def _plus_one(result_from_device):
    def plus_one(*a, **kw):
        r = result_from_device(*a, **kw)
        return dataclasses.replace(r, total_distance=r.total_distance + 1.0)
    return plus_one


def planted(fault: str):
    from repro.core import dist_steiner

    if fault == "sound":
        return contextlib.nullcontext()
    if fault == "exchange_left_out":
        return mock.patch.object(jax.lax, "all_gather", local_only)
    if fault == "unchanged_state":
        return mock.patch.object(dist_steiner, "jax", _NoCandidates())
    if fault == "altered_answer":
        return mock.patch.object(dist_steiner, "result_from_device",
                                 _plus_one(dist_steiner.result_from_device))
    raise ValueError(f"unknown fault {fault!r}")


def main(tmp: str, faults: list) -> None:
    root = make_tiny_root(Path(tmp))
    for fault in ["sound", *faults]:
        jax.clear_caches()
        with planted(fault):
            res = harness.run(root, "mesh4-s18-k16", 2**31 + 5, 1.0, False,
                              time.perf_counter(), require_chip=False)
        print(json.dumps(dict(res, fault=fault)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
