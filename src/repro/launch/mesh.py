"""Device mesh construction.

v5e pod = 256 chips as (data=16, model=16); the multi-pod config stacks a
leading "pod" axis (pure DP across the DCN domain). A FUNCTION, not a
module constant, so importing never touches jax device state.

Every mesh in the repo is built by :func:`make_mesh`, with all axes
``AxisType.Auto``: the solver's shard_map bodies and jitted programs rely
on jax inserting the collectives, which ``jax.make_mesh``'s default
(``Explicit``) axes would turn into type errors.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """All-Auto mesh of ``shape`` over ``devices`` (default: the first
    prod(shape) of ``jax.devices()``, laid out by ``jax.make_mesh``)."""
    import jax
    from jax.sharding import AxisType, Mesh

    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    arr = np.asarray(devices).reshape(tuple(shape))
    return Mesh(arr, tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == ndev:
        return make_mesh(shape, axes)
    if len(devs) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}, have {len(devs)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    # more devices than needed (e.g. 512 forced, single-pod 256): slice
    return make_mesh(shape, axes, devices=devs[:ndev])


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for subprocess tests (8 forced host devices)."""
    return make_mesh(shape, axes)
