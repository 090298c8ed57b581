"""Drive the MST system's main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: phases a-e
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

One chip, at the paper's largest scale (Graph1M_6: 1M vertices, 3M edges):

  a. ``make_solver`` single engine, cas and lock; a same-shape re-solve
     with fresh weights adds no trace and no compile;
  b. ``MSTService()`` with its defaults (batched engine, max_batch=64):
     Graph1M_6, Graph100K_6 and Graph10K_6 requests in one flush, then
     one repeat that must come back from the cache;
  c. the spmm engine through its default candidate path;
  d. a registered Graph100K_6 under 16 interleaved inserts and deletes
     through ``MSTService.update``, then a cache-hit solve;
  e. ``MSTService.cluster`` on 4096 2-D blob points (compiled knn_graph
     kernel) against the brute-force all-pairs reference.

``--chips 4``: the sharded and distributed engines on a 4-chip mesh, and
the single engine on one chip as their baseline, on Graph1M_6.

Every MST mask must equal the Kruskal oracle's bit for bit (the edge set
under the ``(w, u, v)`` order); clustering labels must equal the
reference's.  Each phase prints its wall time, the XLA compiles it ran
and the device's peak memory so far.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check raises; without a TPU the script exits 2 before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MAIN_GRAPH = "Graph1M_6"
SERVE_GRAPHS = ("Graph100K_6", "Graph10K_6")
SERVE_SEEDS = (0, 1, 2)
DYNAMIC_GRAPH = "Graph100K_6"
DYNAMIC_OPS = 16
CLUSTER_POINTS = 4096
CLUSTER_BLOBS = 3
MESH_CHIPS = 4


class SmokeFailure(AssertionError):
    """A result differed from its reference."""


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def expect_mask(got, oracle: np.ndarray, what: str) -> None:
    got = np.asarray(got)
    if got.shape != oracle.shape:
        raise SmokeFailure(f"{what}: mask shape {got.shape} != oracle's "
                           f"{oracle.shape}")
    wrong = int(np.sum(got != oracle))
    expect(wrong == 0, f"{what}: MST mask differs from the Kruskal oracle "
                       f"on {wrong} edges")


def oracle_mask(graph) -> np.ndarray:
    from repro.core.oracle import kruskal_numpy

    return kruskal_numpy(graph.src, graph.dst, graph.weight,
                         graph.num_nodes)[0]


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) from
    JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration_secs


class PhaseClock:
    """Prints one line per phase: wall time, compiles, device peak bytes."""

    def __init__(self, compiles: CompileCounter, devices):
        self.compiles = compiles
        self.devices = devices

    def run(self, name: str, fn, *args):
        n0, s0 = self.compiles.count, self.compiles.seconds
        t0 = time.perf_counter()
        detail = fn(*args)
        wall = time.perf_counter() - t0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        kind = self.devices[0].device_kind
        print(f"phase {name}: wall_s={wall!r} "
              f"compiles={self.compiles.count - n0} "
              f"compile_s={self.compiles.seconds - s0!r} "
              f"peak_bytes_in_use[{kind} x{len(self.devices)}]={peaks} "
              f"{detail}", flush=True)
        return detail


# -- phases -------------------------------------------------------------


def phase_solver(graph, oracle, fresh, fresh_oracle, compiles):
    """a. planned single-engine solver, both paper variants."""
    from repro.core import SolveOptions, make_solver

    out = {}
    for variant in ("cas", "lock"):
        solver = make_solver(SolveOptions(engine="single", variant=variant))
        r = solver.solve(graph)
        expect_mask(r.mst_mask, oracle, f"single/{variant}")
        traces, n0 = solver.stats.traces, compiles.count
        r2 = solver.solve(fresh)
        expect_mask(r2.mst_mask, fresh_oracle, f"single/{variant} re-solve")
        expect(solver.stats.traces == traces,
               f"single/{variant}: same-shape re-solve added a trace")
        expect(compiles.count == n0,
               f"single/{variant}: same-shape re-solve compiled "
               f"{compiles.count - n0} programs")
        out[variant] = (int(r.num_rounds), int(r.num_waves))
    return out


def phase_service(svc, requests, oracles, repeat):
    """b. MSTService defaults: one flush of mixed sizes, then a repeat."""
    responses = svc.solve_many(requests)
    for i, (resp, oracle) in enumerate(zip(responses, oracles)):
        expect_mask(resp.mst_mask, oracle, f"service request {i}")
    again = svc.solve(requests[repeat])
    expect(again.cached, "repeated request was not served from the cache")
    expect_mask(again.mst_mask, oracles[repeat], "cached response")
    return {"requests": len(requests) + 1,
            "buckets": svc.stats.buckets,
            "cache_hits": svc.stats.cache_hits}


def phase_spmm(graph, oracle, single_cas):
    """c. spmm engine through its default candidate-path gate."""
    import jax
    from repro.core import SolveOptions, make_solver
    from repro.core.spmm_mst import resolve_kernel

    path = ("pallas gather_segment_min" if resolve_kernel()
            else "jnp segment_min")
    print(f"spmm candidate path on {jax.default_backend()}: {path}",
          flush=True)
    r = make_solver(SolveOptions(engine="spmm")).solve(graph)
    expect_mask(r.mst_mask, oracle, "spmm/cas")
    got = (int(r.num_rounds), int(r.num_waves))
    expect(got == single_cas,
           f"spmm rounds/waves {got} != single engine's {single_cas}")
    return {"path": path, "rounds_waves": got}


def phase_dynamic(svc, graph, num_ops: int, seed: int):
    """d. streamed inserts and deletes on a registered graph."""
    gid = svc.register_dynamic(graph)
    dyn = svc.dynamic(gid)
    rng = np.random.default_rng(seed)
    n = graph.num_nodes
    for i in range(num_ops):
        if i % 2 == 0:
            u, v = (int(x) for x in rng.choice(n, size=2, replace=False))
            # Light enough to enter the forest and evict a tree edge.
            svc.update(gid, insertions=[(u, v, float(rng.random() * 1e-3))])
        else:
            # Alternate tree and non-tree deletions (cut/reconnect and
            # plain removal).
            g, mask = dyn.graph(), dyn.mask
            pick = np.flatnonzero(mask if i % 4 == 1 else ~mask)
            e = int(rng.choice(pick))
            svc.update(gid, deletions=[(int(g.src[e]), int(g.dst[e]),
                                        float(g.weight[e]))])
    current = dyn.graph()
    oracle = oracle_mask(current)
    expect_mask(dyn.mask, oracle, "dynamic maintained mask")
    r = svc.solve(current)
    expect(r.cached, "solve of the updated graph missed the cache")
    expect_mask(r.mst_mask, oracle, "dynamic cached response")
    return {"updates": num_ops, "edges": current.num_edges}


def phase_cluster(svc, points, num_clusters: int):
    """e. clustering against the brute-force all-pairs reference."""
    from repro.cluster.reference import brute_force_labels

    resp = svc.cluster(points, num_clusters=num_clusters)
    ref = brute_force_labels(points, num_clusters=num_clusters)
    expect(np.array_equal(np.asarray(resp.labels), ref),
           "cluster labels differ from the brute-force reference")
    return {"points": points.shape[0], "knn_k": resp.knn_k,
            "escalations": resp.escalations, "bridges": resp.bridges}


def phase_mesh(graph, oracle, mesh):
    """--chips 4: both mesh engines against the oracle and against the
    single engine's rounds and waves on one chip."""
    from repro.core import SolveOptions, make_solver

    out = {}
    for variant in ("cas", "lock"):
        base = make_solver(SolveOptions(engine="single",
                                        variant=variant)).solve(graph)
        expect_mask(base.mst_mask, oracle, f"single/{variant}")
        want = (int(base.num_rounds), int(base.num_waves))
        out[f"single/{variant}"] = want
        for engine in ("sharded", "distributed"):
            r = make_solver(SolveOptions(engine=engine, variant=variant,
                                         mesh=mesh)).solve(graph)
            expect_mask(r.mst_mask, oracle, f"{engine}/{variant}")
            got = (int(r.num_rounds), int(r.num_waves))
            expect(got == want, f"{engine}/{variant} rounds/waves {got} "
                                f"!= single engine's {want}")
            out[f"{engine}/{variant}"] = got
    return out


# -- entry points -------------------------------------------------------


def run_one_chip(clock, compiles, main_graph, fresh_graph, serve_graphs,
                 dynamic_graph, points, num_clusters) -> None:
    """Phases a-e over the given inputs (one process, one service)."""
    from repro.serve.mst_service import MSTService

    t0 = time.perf_counter()
    main_oracle = oracle_mask(main_graph)
    fresh_oracle = oracle_mask(fresh_graph)
    serve_oracles = [main_oracle] + [oracle_mask(g) for g in serve_graphs]
    print(f"setup: host oracles wall_s={time.perf_counter() - t0!r}",
          flush=True)

    rounds = clock.run("a_solver", phase_solver, main_graph, main_oracle,
                       fresh_graph, fresh_oracle, compiles)
    with MSTService() as svc:
        clock.run("b_service", phase_service, svc,
                  [main_graph] + list(serve_graphs), serve_oracles, 1)
        clock.run("c_spmm", phase_spmm, main_graph, main_oracle,
                  rounds["cas"])
        clock.run("d_dynamic", phase_dynamic, svc, dynamic_graph,
                  DYNAMIC_OPS, 0)
        clock.run("e_cluster", phase_cluster, svc, points, num_clusters)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"1: phases a-e on one chip; {MESH_CHIPS}: only "
                         "the mesh engines against the single engine")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.graphs.generator import generate_points, paper_graph

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    clock = PhaseClock(compiles, devices[:args.chips])

    main_graph = paper_graph(MAIN_GRAPH, seed=0)
    if args.chips == 1:
        run_one_chip(
            clock, compiles, main_graph, paper_graph(MAIN_GRAPH, seed=1),
            [paper_graph(name, seed=s) for name in SERVE_GRAPHS
             for s in SERVE_SEEDS],
            paper_graph(DYNAMIC_GRAPH, seed=0),
            generate_points("blobs", CLUSTER_POINTS, 2, seed=0,
                            num_blobs=CLUSTER_BLOBS),
            CLUSTER_BLOBS)
    else:
        from repro.core.distributed_mst import make_flat_mesh

        oracle = oracle_mask(main_graph)
        clock.run("mesh", phase_mesh, main_graph, oracle,
                  make_flat_mesh(args.chips))

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
