"""Pallas TPU kernels for the paper's two compute hot spots.

The paper spends the majority of runtime in Voronoi-cell relaxation and in
local min-distance cross-cell edge identification (§V-A). Both are
irregular scatter/gather loops on MPI; the TPU-native adaptation makes them
regular:

minplus/  — scatter-free min-plus ELL row reduction (Voronoi relaxation):
            rows = destination vertices (split to width K); XLA gathers
            the neighbours' (dist, lab), the kernel reduces lexicographic
            (dist, lab, pred) minima per row.  On the solver path
            (``mode="pallas"``).
segmin/   — bucketed masked-min segment reduction (cross-cell / COO
            relaxation): edges are pre-bucketed per destination block; the
            kernel replaces scatter-min with a (VB × EB) compare-mask
            reduction.  On no solver path, and not lowered for the TPU.

Each kernel ships ``ops.py`` (jit'd public wrapper) and ``ref.py`` (pure
jnp oracle); tests sweep shapes/dtypes in interpret mode on the CPU.
"""

import jax


def default_interpret() -> bool:
    """Platform policy shared by every Pallas wrapper in this package:
    compiled lowering on TPU/GPU, the interpreter everywhere else (CPU
    has no Mosaic/Triton target).  Wrappers take ``interpret=None`` to
    mean "resolve via this policy"; the solver never overrides it."""
    return jax.default_backend() not in ("tpu", "gpu")
