"""Multi-worker distributed Borůvka demo over every local device.

Runs BOTH mesh engines through the registry: ``distributed`` (edge scan
sharded, topology replicated, DESIGN.md §2) and ``sharded`` (topology
shard-local with the owner-decode collective, DESIGN.md §2a), and prints
each one's per-device topology footprint — the number the sharded engine
exists to shrink.

On a TPU host the mesh spans its chips; on a CPU-only machine it spans 8
forced host devices (the flag only affects the CPU platform and must be
set before JAX initializes).

    PYTHONPATH=src python examples/distributed_mst.py
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core import SolveOptions, make_solver  # noqa: E402
from repro.core.distributed_mst import make_flat_mesh  # noqa: E402
from repro.core.oracle import kruskal_numpy  # noqa: E402
from repro.graphs.generator import generate_graph  # noqa: E402
from repro.graphs.partition_edges import partition_edges  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    n_dev = len(jax.devices())
    print(f"devices: {n_dev} x {jax.devices()[0].device_kind}")
    mesh = make_flat_mesh(n_dev)
    graph = generate_graph(50_000, 6, seed=0)
    oracle_mask, oracle_w, _ = kruskal_numpy(graph.src, graph.dst,
                                             graph.weight, graph.num_nodes)
    part = partition_edges(graph, n_dev)
    # distributed_msf replicates src+dst+order+weight (4 x 4 B/edge) on
    # every device, on top of its 3-array scan shard.
    replicated = graph.num_edges * 4 * 4
    print(f"topology per device: distributed={replicated} B (replicated), "
          f"sharded={part.bytes_per_shard} B "
          f"({replicated / part.bytes_per_shard:.1f}x smaller)")
    for engine in ("distributed", "sharded"):
        for variant in ("cas", "lock"):
            solver = make_solver(SolveOptions(engine=engine,
                                              variant=variant, mesh=mesh))
            r = solver.solve(graph)
            match = bool((np.asarray(r.mst_mask) == oracle_mask).all())
            print(f"{engine:12s} {variant:5s}: "
                  f"weight={float(r.total_weight):.1f} "
                  f"(oracle {oracle_w:.1f}) rounds={int(r.num_rounds)} "
                  f"waves={int(r.num_waves)} exact-match={match}")


if __name__ == "__main__":
    main()
