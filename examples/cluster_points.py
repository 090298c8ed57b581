"""Euclidean-MST clustering demo: points -> kNN kernel -> engine -> labels.

Generates a blob point cloud, clusters it end-to-end through mstserve's
clustering entry point (micro-batched candidate-graph solves + dendrogram
cache), checks the labels against the brute-force all-pairs reference, and
replays the same cloud with a different cut to show the dendrogram-level
cache hit.

    PYTHONPATH=src python examples/cluster_points.py --points 400 --clusters 3
"""
import argparse
import time

import numpy as np

from repro.cluster import brute_force_labels
from repro.core import ENGINES
from repro.graphs.generator import POINT_CLOUDS, generate_points
from repro.serve.mst_service import MSTService
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=400)
    ap.add_argument("--clusters", type=int, default=3)
    ap.add_argument("--kind", default="blobs", choices=POINT_CLOUDS)
    ap.add_argument("--knn-k", type=int, default=8)
    ap.add_argument("--engine", default="batched", choices=sorted(ENGINES))
    ap.add_argument("--variant", default="cas", choices=["cas", "lock"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    pts = generate_points(args.kind, args.points, dim=2, seed=args.seed,
                          num_blobs=args.clusters)
    svc = MSTService(variant=args.variant, engine=args.engine)

    t0 = time.perf_counter()
    resp = svc.cluster(pts, num_clusters=args.clusters, knn_k=args.knn_k)
    dt = time.perf_counter() - t0
    sizes = np.bincount(resp.labels)
    print(f"[cluster] {args.points} points -> {resp.num_clusters} clusters "
          f"{sizes.tolist()} in {dt * 1e3:.1f} ms cold "
          f"(kNN k={resp.knn_k}, {resp.escalations} escalations, "
          f"{resp.bridges} bridge edges, "
          f"{svc.stats.engine_solves} engine solves)")

    if args.points <= 1000:  # brute force is O(n^2) edges
        from repro.cluster.emst import DEFAULT_K

        ref = brute_force_labels(pts, num_clusters=args.clusters)
        agree = float((resp.labels == ref).mean())
        if args.knn_k >= DEFAULT_K:
            assert agree == 1.0, "labels diverge from brute force"
            print("[cluster] labels match the brute-force all-pairs "
                  "reference")
        else:
            # Below the default k the kNN graph can span while missing a
            # true EMST edge (EXPERIMENTS.md §Clustering) — report instead
            # of asserting.
            print(f"[cluster] label agreement vs brute force at "
                  f"k={args.knn_k}: {agree:.1%}")

    # Different cut on the same cloud: dendrogram comes from the LRU.
    cut = float(np.quantile(resp.heights, 0.9))
    t0 = time.perf_counter()
    resp2 = svc.cluster(pts, distance=cut, knn_k=args.knn_k)
    dt = time.perf_counter() - t0
    assert resp2.cached
    print(f"[cluster] re-cut at distance {cut:.3f} -> "
          f"{resp2.num_clusters} clusters in {dt * 1e3:.2f} ms "
          f"(dendrogram cache hit; cluster cache "
          f"{svc.cluster_cache_len} entries)")


if __name__ == "__main__":
    main()
