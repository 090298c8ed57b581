"""Quickstart: the planned-solver API on a paper-style graph.

Configure once (``SolveOptions`` validates eagerly), solve many: the graph
is *sized* (it carries ``num_nodes``), one ``MSTSolver`` per variant runs
both paper hooking schemes, results are verified against the Kruskal
oracle, and a warm re-solve demonstrates the plan cache (0 new traces).

    PYTHONPATH=src python examples/quickstart.py [--nodes 20000] [--degree 6]
    PYTHONPATH=src python examples/quickstart.py --engine opt-seq
"""
import argparse

import numpy as np

from repro.core import ENGINES, SolveOptions, make_solver
from repro.core.oracle import kruskal_numpy
from repro.graphs.generator import generate_graph
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--degree", type=float, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="single", choices=sorted(ENGINES),
                    help="MST engine registry name")
    args = ap.parse_args()

    graph = generate_graph(args.nodes, args.degree, seed=args.seed)
    print(f"graph: {graph.num_nodes} vertices, {graph.num_edges} edges")
    print(f"engine: {args.engine} — {ENGINES[args.engine].description}")

    oracle_mask, oracle_w, _ = kruskal_numpy(graph.src, graph.dst,
                                             graph.weight, graph.num_nodes)
    print(f"oracle (Kruskal): total weight {oracle_w:.2f}")

    for variant in ("cas", "lock"):
        solver = make_solver(SolveOptions(engine=args.engine,
                                          variant=variant))
        r = solver.solve(graph)
        match = bool((np.asarray(r.mst_mask) == oracle_mask).all())
        print(f"{variant:5s}: weight={float(r.total_weight):.2f} "
              f"rounds={int(r.num_rounds)} waves={int(r.num_waves)} "
              f"exact-match={match}")
        # Same shape, fresh weights: the plan cache makes this a warm solve.
        solver.solve(generate_graph(args.nodes, args.degree,
                                    seed=args.seed + 1))
        st = solver.stats
        assert st.traces == 1, "warm re-solve must not retrace"
        print(f"       plan cache: {st.solves} solves, {st.traces} trace, "
              f"{st.plan_hits} hits")


if __name__ == "__main__":
    main()
