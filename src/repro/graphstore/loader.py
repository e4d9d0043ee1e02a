"""Opening ``.gstore`` directories: lazy views over memmapped CSR.

``open_store(path)`` returns a :class:`GraphStore` — a handle whose
arrays stay on disk until touched.  From it you can get

* ``to_graph()``      — the in-memory padded COO :class:`~repro.core.graph.Graph`
                        the solver consumes (materializes O(M) once);
* ``ell(k)``          — the ELLPACK view filled *chunkwise* straight
                        from the CSR (never routes through the COO
                        expansion and re-sort of ``to_ell``);
* ``iter_coo(...)``   — bounded-memory chunks of the directed edge list;
* ``load_partition()``/``load_partition_2d()``/``load_partition_ell()``
  — per-shard loads of a partitioned store, rebuilt into the exact
  ``Partition``/``Partition2D``/``EllPartition`` layouts the mesh
  backends execute.

Checksums are verified at open by default (``verify=False`` skips — e.g.
reopening a store this process just wrote).
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterator, Optional, Tuple, Union

import numpy as np

from repro.graphstore import format as fmt

DEFAULT_COO_CHUNK_EDGES = 1 << 20


class GraphStore:
    """Read-only handle on one on-disk graph.  See :func:`open_store`.

    A store with a non-empty delta log (:mod:`repro.delta`) is opened as
    the base CSR plus a folded COO *overlay*: ``iter_coo`` / ``coo`` /
    ``to_graph`` / ``ell`` transparently yield the EFFECTIVE edge list
    (deletions filtered, reweights applied, additions appended), while
    ``indptr``/``indices``/``weights`` stay the raw base arrays.
    ``epoch`` counts applied delta segments; ``compact()`` (in
    :mod:`repro.delta.compact`) folds the log back into a fresh CSR.
    """

    def __init__(self, path: Union[str, Path], *, verify: bool = True):
        self.path = Path(path)
        self._load_manifest(verify=verify)

    def _load_manifest(self, *, verify: bool) -> None:
        from repro.delta.overlay import fold_overlay

        self.manifest = fmt.read_manifest(self.path)
        if verify:
            fmt.verify_store(self.path, self.manifest)
        self.n: int = int(self.manifest["n"])
        self.m: int = int(self.manifest["m"])
        self.epoch: int = int(self.manifest.get("epoch", 0))
        self.overlay = fold_overlay(self.path, self.manifest)
        self._maps: dict = {}
        self._eff_cache: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None

    def reload(self, *, verify: bool = False) -> "GraphStore":
        """Re-reads the manifest + delta log (after an append/compact by
        this or another process); drops cached memmaps."""
        self._load_manifest(verify=verify)
        return self

    # ------------------------------------------------------------------
    # lazy array views
    # ------------------------------------------------------------------

    def array(self, name: str) -> np.memmap:
        """Memmaps one manifest array (cached per handle)."""
        mm = self._maps.get(name)
        if mm is None:
            mm = fmt.map_array(self.path, self.manifest, name)
            self._maps[name] = mm
        return mm

    @property
    def indptr(self) -> np.memmap:
        return self.array("indptr")

    @property
    def indices(self) -> np.memmap:
        return self.array("indices")

    @property
    def weights(self) -> np.memmap:
        return self.array("weights")

    @property
    def vertex_perm(self) -> Optional[np.ndarray]:
        """old id → stored id map of a hub-sorted store (None otherwise)."""
        if "vertex_perm" not in self.manifest["arrays"]:
            return None
        return self.array("vertex_perm")

    def map_ids(self, ids) -> np.ndarray:
        """Translates original vertex ids (e.g. query seeds) to stored ids."""
        ids = np.asarray(ids)
        perm = self.vertex_perm
        return ids if perm is None else np.asarray(perm)[ids].astype(ids.dtype)

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    @property
    def partition_meta(self) -> Optional[dict]:
        return self.manifest.get("partition")

    @property
    def partition_fresh(self) -> bool:
        """True when persisted shards reflect the store's current epoch.

        Shards written before deltas were appended describe the stale
        base graph; loading them would silently drop the mutations, so
        the shard-load fast paths gate on this.  Re-partitioning (stamps
        the current epoch) or compacting restores freshness.
        """
        meta = self.partition_meta
        if not meta:
            return False
        return self.overlay is None or int(meta.get("epoch", 0)) == self.epoch

    def verify(self) -> None:
        """Re-checks every array + delta segment checksum."""
        fmt.verify_store(self.path, self.manifest)

    # ------------------------------------------------------------------
    # materialization
    # ------------------------------------------------------------------

    def iter_base_coo(
        self, chunk_edges: int = DEFAULT_COO_CHUNK_EDGES
    ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Directed (src, dst, w) chunks of the BASE CSR, bounded memory.

        The delta overlay is NOT applied — most callers want
        :meth:`iter_coo`.
        """
        indptr = np.asarray(self.indptr)
        # cut chunk boundaries on vertex boundaries so src expansion is local
        v = 0
        while v < self.n:
            # largest vertex boundary still within chunk_edges of indptr[v]
            hi = (
                int(np.searchsorted(indptr, indptr[v] + chunk_edges, side="right"))
                - 1
            )
            v_hi = max(v + 1, min(self.n, hi))
            e0, e1 = int(indptr[v]), int(indptr[v_hi])
            counts = np.diff(indptr[v : v_hi + 1]).astype(np.int64)
            src = np.repeat(np.arange(v, v_hi, dtype=np.int32), counts)
            yield src, np.asarray(self.indices[e0:e1]), np.asarray(
                self.weights[e0:e1]
            )
            v = v_hi

    def iter_coo(
        self, chunk_edges: int = DEFAULT_COO_CHUNK_EDGES
    ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """EFFECTIVE directed (src, dst, w) chunks, bounded memory.

        Base-CSR chunks come first (deletions filtered, reweights
        applied — chunks may shrink, even to empty), then the surviving
        delta additions, symmetrized one chunk per append batch.  This
        chunking is the canonical effective edge stream: ``compact()``
        re-ingests exactly it, so per-row arrival order — the part of the
        CSR that is stream-order-sensitive — is reproducible.
        """
        ov = self.overlay
        for s, d, w in self.iter_base_coo(chunk_edges):
            if ov is not None:
                s, d, w = ov.apply_base_chunk(s, d, w)
            yield s, d, w
        if ov is not None:
            yield from ov.iter_add_chunks()

    def coo(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Materializes the full EFFECTIVE directed edge list (O(M) host)."""
        indptr = np.asarray(self.indptr)
        counts = np.diff(indptr).astype(np.int64)
        src = np.repeat(np.arange(self.n, dtype=np.int32), counts)
        if self.overlay is None:
            return src, np.asarray(self.indices), np.asarray(self.weights)
        parts = [
            self.overlay.apply_base_chunk(
                src, np.asarray(self.indices), np.asarray(self.weights)
            )
        ]
        parts.extend(self.overlay.iter_add_chunks())
        return tuple(
            np.concatenate([p[i] for p in parts]) for i in range(3)
        )

    def to_graph(self, *, pad_to: int = 1):
        """Materializes the padded COO :class:`~repro.core.graph.Graph`.

        The store already holds both directions of every edge, so no
        symmetrization happens here.  With a delta overlay the COO is
        expanded from the (cached) effective CSR rather than the edge
        stream, so one ``prepare``/``refresh`` folds the overlay exactly
        once no matter how many views it builds; the relaxation fixpoint
        is edge-order-independent, so this changes nothing downstream.
        """
        from repro.core.graph import from_edges

        if self.overlay is None:
            src, dst, w = self.coo()
        else:
            indptr, dst, w = self.effective_csr()
            src = np.repeat(
                np.arange(self.n, dtype=np.int32),
                np.diff(indptr).astype(np.int64),
            )
        return from_edges(src, dst, w, self.n, symmetrize=False, pad_to=pad_to)

    def effective_csr(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(indptr, indices, weights) of the EFFECTIVE graph, in memory.

        With no overlay this is just host copies of the base memmaps.
        With an overlay, the effective edge stream (:meth:`iter_coo`) is
        folded through the same two-pass builder ``compact()`` persists
        with, so the result is bit-identical to opening the compacted
        store.
        """
        if self.overlay is None:
            return (
                np.asarray(self.indptr),
                np.asarray(self.indices),
                np.asarray(self.weights),
            )
        if self._eff_cache is not None:
            return self._eff_cache
        from repro.graphstore.ingest import csr_two_pass

        def alloc(m: int):
            return np.empty(m, np.int32), np.empty(m, np.float32)

        indptr, indices, weights, _ = csr_two_pass(
            self.n, _EffectiveSource(self), alloc, symmetrize=False
        )
        # cached per manifest load (reload() drops it with the overlay),
        # so to_graph + ell in one prepare fold the overlay once
        self._eff_cache = (indptr, indices, weights)
        return self._eff_cache

    def ell(self, k: int, *, pad_rows_to: int = 1, rows_per_chunk: int = 1 << 16):
        """Split-row ELLPACK view built chunkwise from the CSR.

        Produces exactly what ``core.graph.to_ell`` builds from the
        materialized graph (same row split, same padding aliases), but
        vectorized and without the COO round-trip: rows are filled one
        vertex-chunk at a time, so peak transient memory is the output
        plus one chunk's edge slab.  With a delta overlay the effective
        CSR is built in memory first, then filled by the same code.
        """
        if self.overlay is None:
            indptr, indices, weights = self.indptr, self.indices, self.weights
        else:
            indptr, indices, weights = self.effective_csr()
        from repro.core.graph import ell_from_csr

        return ell_from_csr(
            indptr,
            indices,
            weights,
            self.n,
            k,
            pad_rows_to=pad_rows_to,
            rows_per_chunk=rows_per_chunk,
        )

    # ------------------------------------------------------------------
    # shards
    # ------------------------------------------------------------------

    def _check_shards_fresh(self) -> None:
        # no partition at all is the loaders' own (clearer) error
        if self.partition_meta and not self.partition_fresh:
            raise fmt.StoreFormatError(
                f"{self.path}: persisted shards predate the delta log "
                f"(shard epoch {int((self.partition_meta or {}).get('epoch', 0))}"
                f" != store epoch {self.epoch}); re-partition or compact "
                f"before loading shards"
            )

    def load_partition(self):
        """Rebuilds the stored 1D partition (see ``partition.py``)."""
        from repro.graphstore.partition import load_partition

        self._check_shards_fresh()
        return load_partition(self)

    def load_partition_2d(self):
        """Rebuilds the stored 2D partition (see ``partition.py``)."""
        from repro.graphstore.partition import load_partition_2d

        self._check_shards_fresh()
        return load_partition_2d(self)

    def load_partition_ell(self):
        """Rebuilds the stored 1D ELL partition — the sharded priority-
        queue layout of the mesh frontier mode (see ``partition.py``)."""
        from repro.graphstore.partition import load_partition_ell

        self._check_shards_fresh()
        return load_partition_ell(self)

    def __repr__(self) -> str:
        part = self.partition_meta
        return (
            f"GraphStore({str(self.path)!r}, n={self.n}, m={self.m}, "
            f"partition={part['scheme'] if part else None})"
        )


class _EffectiveSource:
    """Re-iterable edge-source adapter over a store's effective stream
    (what :func:`~repro.graphstore.ingest.csr_two_pass` consumes)."""

    def __init__(self, store: GraphStore):
        self._store = store
        self.n = store.n
        self.describe = f"effective({store.path.name}@{store.epoch})"

    def __iter__(self):
        return self._store.iter_coo()


def open_store(path: Union[str, Path], *, verify: bool = True) -> GraphStore:
    """Opens a ``.gstore`` directory.

    Args:
      path: the store directory.
      verify: check every array's CRC32 against the manifest (streaming,
        bounded memory).  Corruption raises
        :class:`repro.graphstore.format.ChecksumError`; an unknown layout
        version raises :class:`~repro.graphstore.format.StoreFormatError`.
    """
    return GraphStore(path, verify=verify)
