"""What decides ``correct``: the program's answers against the reference.

Every number compared is a count or a gap that a sound run reads as 0, and
every limit is 0: the configuration states exact answers (integer weights,
float32 sums far below 2**24).  ``PERF.md`` gives the readings each limit
was set from.
"""

from __future__ import annotations

import dataclasses
import sys

import numpy as np

from bench.lib import ref as refmod


@dataclasses.dataclass
class HostTree:
    """One answer of a solve, fetched to the host."""

    seeds: np.ndarray
    dist: np.ndarray
    lab: np.ndarray
    pred: np.ndarray
    path_edge: np.ndarray
    bridge_u: np.ndarray
    bridge_v: np.ndarray
    bridge_w: np.ndarray
    bridge_valid: np.ndarray
    total: float
    num_edges: int

    def edges(self):
        """``(u, v, w)`` arrays of the tree's edges, path edges first."""
        n = self.pred.shape[0]
        v = np.flatnonzero(self.path_edge[:n])
        j = np.flatnonzero(self.bridge_valid)
        tu = np.concatenate([self.pred[v], self.bridge_u[j]]).astype(np.int64)
        tv = np.concatenate([v, self.bridge_v[j]]).astype(np.int64)
        dist = self.dist.astype(np.float64)
        tw = np.concatenate([dist[v] - dist[self.pred[v]],
                             self.bridge_w[j].astype(np.float64)])
        return tu, tv, tw


def tree_invalid(g: refmod.Graph, t: HostTree) -> int:
    """0 when the answer is a tree of graph edges with their weights that
    spans the seeds, and whose total is the sum of its edge weights; else
    the number of these guarantees it breaks."""
    import networkx as nx

    bad = 0
    tu, tv, tw = t.edges()
    n = g.n
    qkey = np.minimum(tu, tv) * n + np.maximum(tu, tv)
    uq, inv = np.unique(qkey, return_inverse=True)
    bad += int(uq.size != qkey.size)
    ekey = np.minimum(g.src, g.dst) * n + np.maximum(g.src, g.dst)
    pos = np.minimum(np.searchsorted(uq, ekey), max(uq.size - 1, 0))
    lightest = np.full(uq.size, np.inf)
    if uq.size:
        hit = uq[pos] == ekey
        np.minimum.at(lightest, pos[hit], g.w[hit])
    bad += int(np.any(lightest[inv] != tw))
    tree = nx.Graph()
    tree.add_nodes_from(np.unique(t.seeds).tolist())
    tree.add_edges_from(zip(tu.tolist(), tv.tolist()))
    bad += int(not nx.is_tree(tree))
    bad += int(float(np.sum(tw)) != t.total)
    bad += int(tu.size != t.num_edges)
    return bad


def compare_solve(g: refmod.Graph, answers, rnd=refmod.exact) -> dict:
    """Checks the answers of solve queries; returns ``{name: value}``."""
    out = dict(voronoi_bad=0, tree_diff=0, total_gap=0.0, tree_invalid=0)
    for t in answers:
        r = refmod.steiner(g, t.seeds, rnd)
        n = g.n
        out["voronoi_bad"] += int(np.sum(
            (t.dist[:n] != r.dist) | (t.lab[:n] != r.lab) | (t.pred[:n] != r.pred)))
        tu, tv, _ = t.edges()
        mine = set(zip(np.minimum(tu, tv).tolist(), np.maximum(tu, tv).tolist()))
        out["tree_diff"] += len(mine ^ r.edges)
        out["total_gap"] = max(out["total_gap"], abs(t.total - r.total))
        out["tree_invalid"] += tree_invalid(g, t)
    return out


def compare_served(g: refmod.Graph, answers, rnd=refmod.exact) -> dict:
    """Checks served answers ``(canonical seeds, total, num_edges)``."""
    out = dict(total_gap=0.0, edges_gap=0)
    for seeds, total, num_edges in answers:
        r = refmod.steiner(g, seeds, rnd)
        out["total_gap"] = max(out["total_gap"], abs(total - r.total))
        out["edges_gap"] = max(out["edges_gap"], abs(num_edges - r.num_edges))
    return out


# every number a sound run reads; see PERF.md for the readings behind each
LIMITS = {
    "voronoi_bad": 0,  # vertices whose (dist, lab, pred) differ
    "tree_diff": 0,  # edges in one tree and not the other
    "total_gap": 0.0,  # |total - reference total|, the largest
    "tree_invalid": 0,  # guarantees of a tree broken, summed over answers
    "edges_gap": 0,  # |num_edges - reference|, the largest
    "unanswered": 0,  # requests due in the window that got no answer
}


def verdict(values: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers in
    ``values`` that have a limit; prints each beside its limit on standard
    error, and how many answers were checked."""
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items() if k in LIMITS}
    print(f"checked {values.get('checked')} answers against the reference", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
