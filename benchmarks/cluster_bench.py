"""Clustering section: kNN-EMST pipeline vs brute-force all-pairs MST.

Rows per shape (paired-ratio methodology from ``compaction_bench`` — this
container's wall clock drifts, adjacent pairs survive it):

  * ``cluster_emst_*``  — end-to-end pipeline time (kNN kernel ->
    canonical candidates -> engine solve -> dendrogram) with the derived
    ``speedup_vs_bruteforce`` paired ratio and the pipeline-throughput
    ``points_per_sec`` metric, plus the escalation stats
    (EXPERIMENTS.md §Clustering);
  * ``cluster_brute_*`` — the brute-force side of each pair: complete
    graph (O(n^2) edges) through the same engine + linkage.

Standalone use merges into BENCH_mst.json instead of overwriting it, so
the CI bench-regression job can run just this section on top of the smoke
run:

    PYTHONPATH=src python -m benchmarks.cluster_bench --smoke --json
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Sequence, Tuple

import numpy as np

from benchmarks.bench_io import JSON_PATH, merge_bench_json
from repro.compile_cache import enable_compile_cache

# (kind, n, dim, knn_k) cells.  The smoke cell is a subset of the default
# set so the CI regression job always has a committed baseline key; uniform
# never escalates (k=8 spans), so its paired ratio is the most stable of
# the small shapes.  The blobs cell exercises the full escalation path
# (doubling + exact bridges) inside the measured pipeline.
DEFAULT_SHAPES: Sequence[Tuple[str, int, int, int]] = (
    ("uniform", 256, 2, 8),
    ("blobs", 1024, 2, 8),
    ("uniform", 1024, 2, 8),
    ("ring", 512, 2, 8),
)
SMOKE_SHAPES: Sequence[Tuple[str, int, int, int]] = (
    ("uniform", 256, 2, 8),)


def _brute_graph(points):
    import jax.numpy as jnp
    from repro.cluster.reference import all_pairs_edges
    from repro.core.types import Graph

    u, v, w = all_pairs_edges(points)
    return Graph(jnp.asarray(u), jnp.asarray(v), jnp.asarray(w),
                 num_nodes=points.shape[0])


def cluster_rows(shapes: Sequence[Tuple[str, int, int, int]] = DEFAULT_SHAPES,
                 variant: str = "cas",
                 repeats: int = 5) -> List[Tuple[str, float, str]]:
    """(name, us, derived) rows for the clustering pipeline section."""
    from benchmarks.compaction_bench import paired_time
    from repro.cluster.emst import euclidean_mst
    from repro.cluster.linkage import single_linkage
    from repro.core import SolveOptions, make_solver
    from repro.graphs.generator import generate_points

    brute_solver = make_solver(SolveOptions(variant=variant))
    rows = []
    for kind, n, dim, k in shapes:
        pts = generate_points(kind, n, dim=dim, seed=0)
        bg = _brute_graph(pts)
        bn = bg.num_nodes

        def brute():
            r = brute_solver.solve(bg)
            mask = np.asarray(r.mst_mask)
            u = np.asarray(bg.src)[mask]
            v = np.asarray(bg.dst)[mask]
            w = np.sqrt(np.asarray(bg.weight)[mask])
            return single_linkage(u, v, w, bn)

        last = {}

        def pipe():
            r = last["emst"] = euclidean_mst(pts, k=k, variant=variant)
            return single_linkage(r.src, r.dst, r.distance, r.num_points)

        brute_us, pipe_us, speedup = paired_time(brute, pipe, repeats)
        res = last["emst"]  # escalation stats from the timed runs
        pps = n / (pipe_us * 1e-6)
        rows.append((f"cluster_brute_{kind}{n}_d{dim}_{variant}",
                     brute_us, ""))
        rows.append((
            f"cluster_emst_{kind}{n}_d{dim}_k{k}_{variant}", pipe_us,
            f"speedup_vs_bruteforce={speedup:.3f};"
            f"points_per_sec={pps:.0f};knn_k_final={res.knn_k};"
            f"escalations={res.escalations};bridges={res.bridges}"))
    return rows


def merge_json(rows: List[Tuple[str, float, str]], path: str) -> None:
    """Fold this section's keys into an existing BENCH_mst.json (or start a
    fresh one) without touching other sections' keys.

    Thin wrapper over the shared ``benchmarks.bench_io.merge_bench_json``
    (kept for backward compatibility); this process's obs snapshot rides
    along so the emst_* escalation counters land in ``_metrics``.
    """
    from repro import obs

    merge_bench_json(rows, path, metrics=obs.snapshot())


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shape set for the CI bench-regression job")
    ap.add_argument("--json", action="store_true",
                    help="merge rows into BENCH_mst.json")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    rows = cluster_rows(SMOKE_SHAPES if args.smoke else DEFAULT_SHAPES,
                        repeats=args.repeats)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        merge_json(rows, JSON_PATH)
        print(f"# merged {len(rows)} rows into {JSON_PATH}", file=sys.stderr)


if __name__ == "__main__":
    main()
