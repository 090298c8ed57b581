"""Batched-engine throughput/latency section (mstserve workload).

Measures aggregate graphs/sec of ``batched_msf`` at batch sizes {1, 8, 64}
on one fixed graph class: the scaling signal for the serving subsystem.

The bench class is deliberately *small* (V=64): that is the serving regime —
many tiny user queries — where per-solve dispatch and round-loop overhead
dominate and batching amortizes them across lanes (~2.5-3x aggregate
throughput at b=64 on CPU).  Large graphs are compute-bound and batching is
throughput-neutral there; see EXPERIMENTS.md §Batched for the measured
crossover.
"""
from __future__ import annotations

import time
from typing import List, Tuple

from repro.core.batched_mst import batched_msf, pack_padded
from repro.graphs.batching import bucket_shape
from repro.graphs.generator import generate_graph

BATCH_SIZES = (1, 8, 64)
BENCH_NODES = 64
BENCH_DEGREE = 4

# Weak scaling: per-device edge load is FIXED while the mesh grows — the
# sharded engine's promise is that per-device topology memory stays flat
# (O(E/S)) and only the (V,)-sized collectives grow with the problem.
WEAK_EDGES_PER_DEV = 2048
WEAK_DEVICE_COUNTS = (1, 2, 4, 8)


def weak_scaling_point(n_dev: int, edges_per_dev: int) -> dict:
    """One weak-scaling point: a warm ``sharded_msf`` solve of a ~degree-6
    graph with ``edges_per_dev * n_dev`` edges over an ``n_dev`` mesh
    (best of 3 after one untimed compile)."""
    from repro.core.distributed_mst import make_flat_mesh
    from repro.core.sharded_mst import sharded_msf
    from repro.graphs.partition_edges import partition_edges

    e = edges_per_dev * n_dev
    v = max(16, e // 3)  # ~degree-6 graphs, growing with the mesh
    g = generate_graph(v, 6, seed=n_dev)
    mesh = make_flat_mesh(n_dev)
    part = partition_edges(g, n_dev)

    def run():
        return sharded_msf(g, mesh=mesh, partition=part
                           ).total_weight.block_until_ready()

    run()  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return {"n_dev": n_dev, "num_edges": g.num_edges, "num_nodes": v,
            "us": best * 1e6, "edges_per_dev": part.shard_edges,
            "topology_bytes_per_dev": part.bytes_per_shard}


def weak_scaling_rows(edges_per_dev: int = WEAK_EDGES_PER_DEV,
                      device_counts=WEAK_DEVICE_COUNTS
                      ) -> List[Tuple[str, float, str]]:
    """Sharded-engine weak scaling: mesh sizes swept with a constant
    per-device edge load, over the chips of a TPU host or forced host
    devices on a CPU (``mst_figures.run_mesh_sizes``).

    The derived column records the per-device topology footprint — the
    number BENCH_mst.json tracks across PRs to catch replication creeping
    back in.
    """
    from benchmarks.mst_figures import run_mesh_sizes

    rows = []
    for r in run_mesh_sizes(weak_scaling_point, device_counts,
                            edges_per_dev=edges_per_dev):
        rows.append((
            f"sharded_weak_e{edges_per_dev}_d{r['n_dev']}", r["us"],
            f"edges_per_dev={r['edges_per_dev']};"
            f"topology_bytes_per_dev={r['topology_bytes_per_dev']};"
            f"V={r['num_nodes']};E={r['num_edges']}"))
    return rows


def batched_throughput_rows(batch_sizes=BATCH_SIZES, *,
                            num_nodes: int = BENCH_NODES,
                            degree: int = BENCH_DEGREE,
                            variant: str = "cas",
                            repeats: int = 3) -> List[Tuple[str, float, str]]:
    """(name, us_per_call, derived) rows; derived carries graphs_per_sec.

    Also records the ``b64_vs_b8`` same-run throughput ratio — batching
    amortizes dispatch across lanes, so aggregate graphs/sec must not
    fall as lanes grow; the ratio is a gated metric
    (``scripts/check_bench_regression.py``) after a regression shipped
    where b=64 throughput silently dropped below b=8.
    """
    rows = []
    gps_by_b = {}
    for b in batch_sizes:
        graphs = [generate_graph(num_nodes, degree, seed=s)
                  for s in range(b)]
        e_pad, v_pad = bucket_shape(graphs[0].num_edges, num_nodes)
        packed = pack_padded(graphs, padded_edges=e_pad, padded_nodes=v_pad)

        def run():
            return batched_msf(packed, num_nodes=v_pad, variant=variant
                               ).total_weight.block_until_ready()

        run()  # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        us = best * 1e6
        gps = b / best
        gps_by_b[b] = gps
        rows.append((f"batched_msf_{variant}_V{num_nodes}_b{b}", us,
                     f"graphs_per_sec={gps:.1f}"))
    if 8 in gps_by_b and 64 in gps_by_b:
        rows.append((f"batched_scaling_{variant}_V{num_nodes}", 0.0,
                     f"b64_vs_b8={gps_by_b[64] / gps_by_b[8]:.3f}"))
    return rows


def batched_e2e_rows(batch_sizes=(8, 64), *,
                     num_nodes: int = BENCH_NODES,
                     degree: int = BENCH_DEGREE,
                     variant: str = "cas",
                     repeats: int = 3) -> List[Tuple[str, float, str]]:
    """End-to-end ``solve_many`` throughput: lane packing + engine solve +
    per-lane result trimming.

    The engine-only rows above can't see host-side pack/unpack costs; this
    is the row that moved when the per-graph transfer loop in
    ``pack_padded`` and the per-lane scalar boxing in
    ``unpack_results_mst`` were vectorized.
    """
    from repro.core.solver import make_solver

    rows = []
    for b in batch_sizes:
        graphs = [generate_graph(num_nodes, degree, seed=s)
                  for s in range(b)]
        solver = make_solver(engine="batched", variant=variant)
        solver.solve_many(graphs)  # compile + warm plan cache
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            solver.solve_many(graphs)
            best = min(best, time.perf_counter() - t0)
        us = best * 1e6
        rows.append((f"batched_e2e_{variant}_V{num_nodes}_b{b}", us,
                     f"graphs_per_sec={b / best:.1f}"))
    return rows
