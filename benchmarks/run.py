"""Benchmark runner: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default scope is the reduced
graph sweep (10K/100K); pass --full for the paper's 1M-vertex classes and
--scaling for the multi-device scaling figures (subprocess per worker
count).  --json additionally merges the rows into ``BENCH_mst.json``
(``{name: us_per_call}`` + ``_derived`` + a ``_metrics`` obs snapshot)
through ``benchmarks.bench_io`` so the perf trajectory is
machine-readable across PRs and sections written by other entry points
(``cluster_bench --smoke --json``) survive.
"""
from __future__ import annotations

import argparse
import sys

from benchmarks.bench_io import JSON_PATH, merge_bench_json, phase_split
from repro.compile_cache import enable_compile_cache


def solver_cache_rows(graph_name: str, repeats: int):
    """Plan-cache telemetry rows: trace count vs solve count on repeated
    same-shape solves, per engine.

    ``warm_hit_rate`` (plan hits / dispatches) is the retrace-regression
    tripwire: a warm solver re-solving a seen shape must hit its plan
    cache, so the rate is deterministic (N solves, 1 trace -> (N-1)/N) and
    any engine change that starts re-tracing warm shapes drops it through
    ``scripts/check_bench_regression.py``'s tolerance.
    """
    import time

    import jax
    import numpy as np

    from repro.core import SolveOptions, make_solver
    from repro.graphs.generator import PAPER_GRAPHS, generate_graph

    n, deg = PAPER_GRAPHS[graph_name]
    rows = []
    for engine in ("single", "batched"):
        solver = make_solver(SolveOptions(engine=engine))
        solver.solve(generate_graph(n, deg, seed=0))  # cold: compiles
        times = []
        for s in range(1, repeats + 1):  # same shape, fresh weights
            g = generate_graph(n, deg, seed=s)
            t0 = time.perf_counter()
            jax.block_until_ready(solver.solve(g))
            times.append(time.perf_counter() - t0)
        st = solver.stats
        rows.append((
            f"solver_cache_{engine}_{graph_name}",
            float(np.median(times)) * 1e6,
            f"traces={st.traces};solves={st.solves};"
            f"warm_hit_rate={st.warm_hit_rate:.3f}",
            phase_split(solver.last_trace)))
    return rows


def main() -> None:
    enable_compile_cache()
    from repro.core import ENGINES

    engine_help = "; ".join(f"{name}: {spec.description}"
                            for name, spec in sorted(ENGINES.items()))
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the 1M-vertex Table 1 classes")
    ap.add_argument("--scaling", action="store_true",
                    help="run fig2/3/4 multi-device scaling (subprocesses)")
    ap.add_argument("--graph", default="Graph100K_6")
    ap.add_argument("--engine", default="single", choices=sorted(ENGINES),
                    help="MST engine for the single-process comparison — "
                         + engine_help)
    ap.add_argument("--no-weak", action="store_true",
                    help="skip the sharded weak-scaling subprocess section")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_mst.json next to the CSV output")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repetitions per row (median reported) after "
                         "one untimed warmup solve per (engine, variant, "
                         "shape); the paired compaction section floors "
                         "this at 5 — its median-of-ratios needs the "
                         "pairs")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shape set for the CI bench-regression "
                         "job: small graphs, no subprocess sections")
    args = ap.parse_args()

    from benchmarks import (compaction_bench, kernel_bench, mst_figures,
                            roofline_bench)

    rows = []
    graphs = (["Graph10K_3", "Graph10K_6"] if args.smoke
              else list(mst_figures.DEFAULT_GRAPHS))
    if args.full:
        graphs += mst_figures.FULL_EXTRA
    rows += mst_figures.fig1_sequential_optimization(graphs,
                                                     repeats=args.repeats)
    if args.scaling:
        rows += mst_figures.fig23_parallel_scaling("lock", args.graph)
        rows += mst_figures.fig23_parallel_scaling("cas", args.graph)
        rows += mst_figures.fig4_cas_vs_lock(args.graph)
    else:
        # single-process variant comparison (structural metrics + wall time)
        # through one planned solver per variant (--engine picks the path).
        import jax

        from repro.core import SolveOptions, make_solver
        from repro.graphs.generator import paper_graph
        gname = "Graph10K_6" if args.smoke else args.graph
        g = paper_graph(gname, seed=0)
        for variant in ("cas", "lock"):
            solver = make_solver(SolveOptions(engine=args.engine,
                                              variant=variant))
            # jax.block_until_ready handles both result flavours: device
            # arrays (per-graph engines) and the lane-packed path's
            # already-synced host arrays.
            fn = lambda: jax.block_until_ready(solver.solve(g))
            us = mst_figures._time(fn, reps=args.repeats)
            r = solver.solve(g)
            # 4th element: the warm solve's rank/pack/solve wall split,
            # persisted under _phases for the regression gate's
            # phase attribution (scripts/check_bench_regression.py).
            rows.append((f"fig23_{gname}_{variant}_{args.engine}_1proc",
                         us,
                         f"rounds={int(r.num_rounds)};"
                         f"waves={int(r.num_waves)}",
                         phase_split(solver.last_trace)))
    # Planned-solver plan-cache telemetry: deterministic retrace tripwire.
    # Same graph class in smoke and full runs so the CI regression job
    # always has a committed baseline key to compare.
    rows += solver_cache_rows("Graph10K_6", repeats=max(args.repeats, 5))
    # Frontier compaction vs uncompacted, same engine (paired ratios), plus
    # the per-round live-edge decay traces.
    rows += compaction_bench.compaction_rows(
        cells=(compaction_bench.SMOKE_CELLS if args.smoke
               else compaction_bench.DEFAULT_CELLS),
        repeats=max(args.repeats, 5))
    # spmm engine vs the edge-list single engine (paired ratios): the
    # semiring SpMV candidate selection's gated headline speedup.
    from benchmarks import spmm_bench
    rows += spmm_bench.spmm_rows(
        cells=(spmm_bench.SMOKE_CELLS if args.smoke
               else spmm_bench.DEFAULT_CELLS),
        repeats=max(args.repeats, 5))
    # Dynamic-MSF layer: one-edge incremental update vs the full re-solve
    # it replaces (paired ratios) — the update path's gated headline.
    from benchmarks import dynamic_bench
    rows += dynamic_bench.dynamic_rows(
        cells=(dynamic_bench.SMOKE_CELLS if args.smoke
               else dynamic_bench.DEFAULT_CELLS),
        repeats=max(args.repeats, 5))
    # Batched multi-graph engine: serving throughput at batch {1, 8, 64},
    # plus end-to-end solve_many rows (pack + solve + unpack) that see the
    # host-side lane packing costs the engine-only rows cannot.
    from benchmarks import batched_bench
    rows += batched_bench.batched_throughput_rows(repeats=args.repeats)
    rows += batched_bench.batched_e2e_rows(repeats=args.repeats)
    # Euclidean-MST clustering pipeline vs brute-force all-pairs (paired).
    # Smoke runs skip it: the CI bench-regression job runs the standalone
    # `benchmarks.cluster_bench --smoke --json` step, which merges its keys
    # into BENCH_mst.json — including it here too would time the same cell
    # twice per CI run.
    if not args.smoke:
        from benchmarks import cluster_bench
        rows += cluster_bench.cluster_rows(cluster_bench.DEFAULT_SHAPES,
                                           repeats=max(args.repeats, 5))
    # Service telemetry: frozen request stream, deterministic hit_rate and
    # p50/p90/p99 flush-latency derived metrics (runs in smoke too — the
    # CI metrics-schema step needs the mstserve_* keys in the snapshot).
    from benchmarks import serve_bench
    rows += serve_bench.serve_rows()
    if not (args.no_weak or args.smoke):
        # Sharded-engine weak scaling (forced 8-host-device subprocess):
        # per-device topology bytes land in BENCH_mst.json's derived column.
        rows += batched_bench.weak_scaling_rows()

    rows += kernel_bench.all_rows()
    rows += roofline_bench.all_rows()

    print("name,us_per_call,derived")
    for row in rows:  # rows are (name, us, derived[, phases])
        print(f"{row[0]},{row[1]:.1f},{row[2]}")

    if args.json:
        from repro import obs

        # Merge-preserving write: rows land under their own keys,
        # non-timing metrics under "_derived", and the full process-wide
        # telemetry snapshot (every MetricsRegistry this run created)
        # under "_metrics" — scripts/dump_metrics.py renders it.
        merge_bench_json(rows, JSON_PATH, metrics=obs.snapshot())
        print(f"# wrote {JSON_PATH}", file=sys.stderr)


if __name__ == "__main__":
    main()
