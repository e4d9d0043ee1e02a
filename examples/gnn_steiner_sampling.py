"""Steiner-tree-driven GNN training — the paper's technique feeding a GNN.

    PYTHONPATH=src python examples/gnn_steiner_sampling.py

The paper's use case (§I) is explaining connections between seed entities.
Here the Steiner engine becomes a *subgraph sampler* for GNN training:
for each batch of labeled seed vertices, the 2-approx Steiner tree
connecting them (plus its 1-hop halo) is the training subgraph — a
connectivity-aware alternative to random fanout sampling that shares the
core library end-to-end (same graph container, same partitioner family).
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.configs.base import ShapeSpec
from repro.core import from_edges, steiner_tree
from repro.data.graphs import rmat_edges
from repro.models import gnn as gnn_mod
from repro.optim import OptConfig, adamw_init


def steiner_subgraph(g, src, dst, seeds, n):
    """Vertices of the Steiner tree + 1-hop halo, as a relabeled subgraph."""
    res = steiner_tree(g, jnp.asarray(seeds))
    marked = np.asarray(res.tree.in_tree_vertex)
    halo = marked.copy()
    halo[src[marked[dst]]] = True  # 1-hop in-neighbors of tree vertices
    halo[dst[marked[src]]] = True
    verts = np.nonzero(halo)[0]
    remap = -np.ones(n, np.int64)
    remap[verts] = np.arange(len(verts))
    keep = halo[src] & halo[dst]
    e = np.stack([remap[src[keep]], remap[dst[keep]]], 1).astype(np.int32)
    return verts, e, float(res.tree.total_distance)


def main() -> None:
    enable_compile_cache()
    rng = np.random.default_rng(0)
    src, dst, w, n = rmat_edges(11, 8, max_weight=50, seed=3)
    g = from_edges(src, dst, w, n, pad_to=64)
    # synthetic node features/labels: label = community-ish hash
    feats = rng.normal(size=(n, 16)).astype(np.float32)
    labels = (np.arange(n) * 2654435761 % 5).astype(np.int32)

    cfg = get_arch("graphsage-reddit").reduced
    params = gnn_mod.init_params(cfg, 16, jax.random.PRNGKey(0))
    opt_cfg = OptConfig(lr=1e-2)
    opt_state = adamw_init(params, opt_cfg)

    losses = []
    for step in range(8):
        seeds = rng.choice(n, size=12, replace=False).astype(np.int32)
        verts, sub_edges, D = steiner_subgraph(g, src, dst, seeds, n)
        shape = ShapeSpec(
            name="steiner_batch", kind="gnn_full",
            n_nodes=len(verts), n_edges=len(sub_edges), d_feat=16,
        )
        # NOTE: subgraph sizes vary per batch → re-jit per shape bucket; a
        # production run pads to fixed buckets (as the dry-run cells do).
        train = jax.jit(gnn_mod.make_train_step(cfg, shape, opt_cfg))
        batch = {
            "x": jnp.asarray(feats[verts]),
            "edges": jnp.asarray(sub_edges),
            "labels": jnp.asarray(labels[verts]),
        }
        params, opt_state, loss = train(params, opt_state, batch)
        losses.append(float(loss))
        print(
            f"step {step}: steiner D={D:7.0f}, subgraph "
            f"|V|={len(verts):5d} |E|={len(sub_edges):6d}, loss={losses[-1]:.4f}"
        )
    assert losses[-1] < losses[0]
    print("GNN learns on Steiner-sampled subgraphs: OK")


if __name__ == "__main__":
    main()
