"""MST §Perf iterations (wall-clock on CPU - a real runtime for this
workload - plus structural metrics).

    PYTHONPATH=src python scripts/mst_perf.py [--graph Graph1M_9]
"""
import argparse
import time

import jax

from repro.core.mst import (minimum_spanning_forest, mst_optimized,
                            mst_unoptimized)
from repro.graphs.generator import paper_graph
from repro.compile_cache import enable_compile_cache


def t(fn, reps=2):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="Graph1M_9")
    args = ap.parse_args()
    g = paper_graph(args.graph, seed=0)
    print(f"graph {args.graph}: V={g.num_nodes} E={g.num_edges}")

    r = minimum_spanning_forest(g, variant="cas")
    print(f"cas engine: rounds={int(r.num_rounds)}")

    rows = {}
    rows["engine_cas(jit while, masked)"] = t(
        lambda: minimum_spanning_forest(g, variant="cas")
        .total_weight.block_until_ready())
    rows["engine_cas(no covered mask)"] = t(
        lambda: minimum_spanning_forest(g, variant="cas",
                                        track_covered=False)
        .total_weight.block_until_ready())
    rows["python_unopt (paper unoptimized)"] = t(
        lambda: mst_unoptimized(g).total_weight.block_until_ready(),
        reps=1)
    rows["python_opt (paper covered+compaction)"] = t(
        lambda: mst_optimized(g).total_weight.block_until_ready(),
        reps=1)
    for waves in (4, 16, 64):
        rl = minimum_spanning_forest(g, variant="lock",
                                     max_lock_waves=waves)
        rows[f"engine_lock(waves<={waves})"] = t(
            lambda: minimum_spanning_forest(
                g, variant="lock", max_lock_waves=waves)
            .total_weight.block_until_ready())
        rows[f"engine_lock(waves<={waves})_meta"] = (
            int(rl.num_rounds), int(rl.num_waves))

    for k, val in rows.items():
        if isinstance(val, tuple):
            print(f"{k:44s} rounds={val[0]} waves={val[1]}")
        else:
            print(f"{k:44s} {val * 1e3:9.1f} ms")


if __name__ == "__main__":
    main()
