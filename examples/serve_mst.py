"""mstserve demo: micro-batched MST query serving with a result cache.

Simulates a request stream of mixed-size graphs (the "millions of users"
workload at toy scale): submit N graphs, flush once — requests bucket by
padded shape and solve as vmapped batches — then replay a hot subset to
show cache hits.

    PYTHONPATH=src python examples/serve_mst.py --requests 32 --variant cas
"""
import argparse
import time

import numpy as np

from repro.core import ENGINES
from repro.core.oracle import kruskal_numpy
from repro.graphs.generator import generate_graph
from repro.serve.mst_service import MSTService
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--variant", default="cas", choices=["cas", "lock"])
    ap.add_argument("--engine", default="batched", choices=sorted(ENGINES),
                    help="registry engine behind the service (batched = "
                         "lane-parallel; others solve per request)")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.requests < 1:
        ap.error("--requests must be >= 1")

    rng = np.random.default_rng(args.seed)
    svc = MSTService(variant=args.variant, engine=args.engine,
                     max_batch=args.max_batch)

    reqs = []
    for i in range(args.requests):
        n = int(rng.integers(20, 400))
        deg = int(rng.integers(2, 7))
        reqs.append(generate_graph(n, deg, seed=args.seed + i))

    t0 = time.perf_counter()
    responses = svc.solve_many(reqs)
    dt = time.perf_counter() - t0

    # Spot-check one response against the Kruskal oracle.
    g = reqs[0]
    om, ow, _ = kruskal_numpy(g.src, g.dst, g.weight, g.num_nodes)
    assert (responses[0].mst_mask == om).all()
    print(f"[mstserve] {len(responses)} requests in {dt * 1e3:.1f} ms "
          f"({len(responses) / dt:.1f} graphs/s cold) "
          f"across {svc.stats.buckets} shape buckets "
          f"{sorted(svc.stats.bucket_shapes)}")

    hot = reqs[: max(1, args.requests // 4)]
    t0 = time.perf_counter()
    again = svc.solve_many(hot)
    dt = time.perf_counter() - t0
    assert all(r.cached for r in again)
    print(f"[mstserve] replayed {len(hot)} hot requests in "
          f"{dt * 1e3:.2f} ms — cache hits {svc.stats.cache_hits}, "
          f"engine solves {svc.stats.engine_solves}, "
          f"cache size {svc.cache_len}")
    st = svc.solver.stats
    print(f"[mstserve] solver plan cache: {st.traces} traces for "
          f"{st.batches} engine calls ({st.plan_hits} warm hits)")


if __name__ == "__main__":
    main()
