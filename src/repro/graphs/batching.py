"""Shape bucketing for the batched MST engine.

``batched_msf`` is jitted on the padded shapes ``(B, E_pad)`` x ``V_pad``:
every distinct shape is a recompile.  The single-graph engine already bounds
its compaction shapes by padding survivor counts to the next power of two
(``core/mst._python_loop``); this module applies the same idiom at the
*batch* level — every graph is rounded up to a power-of-two (edge, vertex)
bucket, so a stream of arbitrary request sizes compiles at most
``log2(E_max) * log2(V_max)`` engine variants, and in practice a handful.

``pack_graphs`` groups a request list into buckets; ``unpack_results``
scatters per-lane results back to the original request order.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import numpy as np

from repro.core.batched_mst import (BatchedGraph, BatchedMSTResult,
                                    pack_padded)
from repro.core.types import GraphLike, as_request
from repro.obs.trace import phase as _obs_phase

MIN_BUCKET = 64  # below this, shapes collapse into one tiny bucket


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, MIN_BUCKET)."""
    n = max(int(n), MIN_BUCKET)
    return 1 << (n - 1).bit_length()


def bucket_shape(num_edges: int, num_nodes: int) -> Tuple[int, int]:
    """(E_pad, V_pad) power-of-two bucket for one graph."""
    return next_pow2(num_edges), next_pow2(num_nodes)


class PackedBucket(NamedTuple):
    """One shape bucket of the packed request list.

    Attributes:
      graph:        padded BatchedGraph, one lane per member graph.
      padded_nodes: V_pad — the static ``num_nodes`` to pass to
                    ``batched_msf``.
      indices:      original position (into the ``pack_graphs`` input) of
                    each lane.
    """

    graph: BatchedGraph
    padded_nodes: int
    indices: List[int]

    @property
    def padded_edges(self) -> int:
        return self.graph.padded_edges


def pack_graphs(graphs: Sequence[GraphLike],
                *, max_batch: int | None = None) -> List[PackedBucket]:
    """Group solve requests into power-of-two buckets.

    Args:
      graphs: request list — sized :class:`Graph` objects (or legacy
        ``(graph, num_nodes)`` pairs); order defines the index space that
        ``unpack_results`` restores.
      max_batch: optional cap on lanes per bucket (micro-batching); buckets
        overflow into multiple PackedBuckets of the same shape.
    """
    sized = [as_request(g) for g in graphs]
    by_shape: Dict[Tuple[int, int], List[int]] = {}
    for i, g in enumerate(sized):
        by_shape.setdefault(bucket_shape(g.num_edges, g.num_nodes),
                            []).append(i)

    buckets: List[PackedBucket] = []
    for (e_pad, v_pad), idxs in sorted(by_shape.items()):
        for lo in range(0, len(idxs), max_batch or len(idxs)):
            chunk = idxs[lo:lo + (max_batch or len(idxs))]
            bg = pack_padded([sized[i] for i in chunk],
                             padded_edges=e_pad, padded_nodes=v_pad)
            buckets.append(PackedBucket(bg, v_pad, list(chunk)))
    return buckets


def unpack_results_mst(buckets: Sequence[PackedBucket],
                       results: Sequence[BatchedMSTResult]
                       ) -> List["MSTResult"]:
    """Scatter per-lane results back to original request order, as full
    :class:`~repro.core.types.MSTResult` records (host numpy arrays)
    trimmed to each graph's true sizes — the identity inverse of
    ``pack_graphs``.  The single lane-trim implementation every bulk
    consumer (``MSTSolver.solve_many``, mstserve) builds on.
    """
    from repro.core.types import MSTResult

    n = sum(len(b.indices) for b in buckets)
    out: List[MSTResult] = [None] * n  # type: ignore[list-item]
    with _obs_phase("trim"):
        # ONE device->host transfer for all buckets (not per bucket, and
        # not per lane per field) — at high lane counts the per-bucket
        # sync was a visible slice of batched throughput.
        results_np = jax.device_get(list(results))
        for bucket, res_np in zip(buckets, results_np):
            # Bulk-convert the per-lane scalars once: python ints/floats
            # out of one .tolist() each, instead of boxing a numpy scalar
            # per lane per field inside the loop.
            nn = np.asarray(bucket.graph.num_nodes).tolist()
            ne = np.asarray(bucket.graph.num_edges).tolist()
            rounds = res_np.num_rounds.tolist()
            waves = res_np.num_waves.tolist()
            totals = res_np.total_weight.tolist()
            comps = res_np.num_components.tolist()
            parent, mask = res_np.parent, res_np.mst_mask
            for lane, orig in enumerate(bucket.indices):
                # parent/mst_mask slices are views into the bucket arrays
                # — no per-lane copy.
                out[orig] = MSTResult(
                    parent=parent[lane, :nn[lane]],
                    mst_mask=mask[lane, :ne[lane]],
                    num_rounds=rounds[lane],
                    num_waves=waves[lane],
                    total_weight=totals[lane],
                    num_components=comps[lane])
    return out


def unpack_results(buckets: Sequence[PackedBucket],
                   results: Sequence[BatchedMSTResult]) -> List[tuple]:
    """Legacy tuple view of :func:`unpack_results_mst`: per-graph
    ``(mst_mask, parent, total_weight, num_components, num_rounds)``."""
    return [(r.mst_mask, r.parent, float(r.total_weight),
             int(r.num_components), int(r.num_rounds))
            for r in unpack_results_mst(buckets, results)]
