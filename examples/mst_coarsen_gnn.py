"""The paper's technique as a framework feature: Borůvka coarsening inside
a GNN pipeline.

Trains GIN on a synthetic node-classification graph, then pools the graph
with one round of Borůvka hooking (core/coarsen.py) and reports the
coarse-graph statistics + pooled-feature readout - the hierarchical-GNN
use case for parallel MST (DESIGN.md §5).

    PYTHONPATH=src python examples/mst_coarsen_gnn.py
"""
import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.core.coarsen import boruvka_coarsen, coarsen_edges, \
    coarsen_features
from repro.models.gnn import gnn_loss, init_gnn_params
from repro.train import data as data_lib
from repro.train.train_loop import run_training
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = ARCHS["gin-tu"].smoke
    n, e, d, classes = 600, 2400, 16, 5
    key = jax.random.key(0)
    batch = data_lib.gnn_full_batch(cfg, n=n, e=e, d_feat=d,
                                    classes=classes, key=key)

    params, metrics = run_training(
        cfg=cfg,
        init_params_fn=lambda k: init_gnn_params(k, cfg, d_in=d,
                                                 num_classes=classes),
        loss_fn=gnn_loss, batch_fn=lambda k: batch, num_steps=20,
        lr=3e-3, log_every=10)
    print(f"[gnn] trained: {metrics}")

    # Borůvka pooling: weight edges by feature distance, coarsen, pool.
    from repro.core.types import Graph
    feat = batch["node_feat"]
    dist = jnp.linalg.norm(feat[batch["edge_src"]]
                           - feat[batch["edge_dst"]], axis=-1)
    g = Graph(batch["edge_src"], batch["edge_dst"], dist)
    c = boruvka_coarsen(g, num_nodes=n, num_rounds=1)
    nc = int(c.num_clusters)
    pooled = coarsen_features(feat, c, num_clusters=n)[:nc]
    cu, cv, mask = coarsen_edges(g, c)
    print(f"[coarsen] {n} nodes -> {nc} clusters "
          f"({int(mask.sum())} cross-cluster edges); "
          f"pooled features {pooled.shape}, finite="
          f"{bool(jnp.isfinite(pooled).all())}")


if __name__ == "__main__":
    main()
