"""Helper of ``test_faults.py``: a sound and a broken run of the mesh cell on
four virtual CPU devices, one result line each.  Run with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent))
from conftest import make_tiny_root  # noqa: E402

from bench.lib import harness  # noqa: E402


def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
    """An all-gather over four chips that returns this chip's block four times."""
    parts = [x] * 4
    return jnp.concatenate(parts, axis=axis) if tiled else jnp.stack(parts, axis=axis)


def main(tmp: str) -> None:
    root = make_tiny_root(Path(tmp))
    for broken in (False, True):
        if broken:
            jax.clear_caches()
            jax.lax.all_gather = local_only
        res = harness.run(root, "mesh4-s18-k16", 2**31 + 5, 1.0, False, time.perf_counter(),
                          require_chip=False)
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
