"""Batched multi-graph Borůvka MSF — the unit of work becomes a *batch*.

Durbhakula (2020) evaluates one solve at a time; serving MST queries at
production scale means many small/medium graphs in flight at once.  Sparse
MSF formulations (Baer et al.) and "Engineering Massively Parallel MST
Algorithms" both get their throughput from regular batched data-parallel
kernels, and the single-graph engine in ``core/mst.py`` is already pure SPMD
dataflow — so the whole engine vmaps (DESIGN.md §3).

Layout: a :class:`BatchedGraph` packs ``B`` graphs into padded ``(B, E_pad)``
edge arrays plus per-lane true sizes.  Padding is *sentinel-rank* padding:

  * pad edges are self-loops ``(0, 0)`` with ``+inf`` weight — a self-loop is
    "covered" in round 1, so its rank key becomes ``INT_SENTINEL`` and it
    never becomes a candidate;
  * pad vertices are isolated — no edge touches them, so they stay singleton
    roots and are subtracted from ``num_components`` at the end.

Every lane therefore converges independently inside ONE ``lax.while_loop``
(the loop runs until the *slowest* lane finishes; finished lanes round-trip
as no-ops: no candidates => parent/mask/rounds all fixed).  Shape bucketing
to bound recompiles lives in ``graphs/batching.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (ContractCarry, boruvka_contract_epoch,
                               boruvka_epoch, contracted_parent_original_ids,
                               init_frontier, materialize_commits,
                               scan_bucket_sizes, validate_variant,
                               vertex_bucket_sizes)
from repro.core.mst import boruvka_round, rank_edges, _init_state
from repro.core.types import GraphLike, as_request
from repro.core.union_find import count_components
from repro.obs.trace import phase as _obs_phase

PAD_WEIGHT = jnp.float32(jnp.inf)  # sorts after every real weight


class BatchedGraph(NamedTuple):
    """``B`` edge-list graphs packed into one padded pytree.

    Attributes:
      src:       (B, E_pad) int32; pad lanes hold self-loops (0, 0).
      dst:       (B, E_pad) int32.
      weight:    (B, E_pad) float32; pad entries are +inf.
      num_nodes: (B,) int32 true vertex count per lane (<= padded V).
      num_edges: (B,) int32 true edge count per lane (<= E_pad).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    weight: jnp.ndarray
    num_nodes: jnp.ndarray
    num_edges: jnp.ndarray

    @property
    def batch_size(self) -> int:
        return int(self.src.shape[0])

    @property
    def padded_edges(self) -> int:
        return int(self.src.shape[1])


class BatchedMSTResult(NamedTuple):
    """Per-lane forest results (padded shapes; trim with ``num_*``).

    ``num_components`` already excludes pad vertices, so a connected lane
    reads 1 regardless of padding.
    """

    parent: jnp.ndarray          # (B, V_pad)
    mst_mask: jnp.ndarray        # (B, E_pad)
    num_rounds: jnp.ndarray      # (B,)
    num_waves: jnp.ndarray       # (B,)
    total_weight: jnp.ndarray    # (B,)
    num_components: jnp.ndarray  # (B,) pad-singleton corrected


def pack_padded(graphs: Sequence[GraphLike], *, padded_edges: int,
                padded_nodes: int) -> BatchedGraph:
    """Stack sized graphs (or legacy ``(graph, num_nodes)`` pairs) into one
    padded BatchedGraph.

    Host-side (numpy) construction; callers wanting automatic power-of-two
    bucketing should go through ``graphs.batching.pack_graphs``.

    The lane fill is vectorized: ONE ``jax.device_get`` fetches every
    graph's arrays (a per-graph ``np.asarray`` is a synchronous transfer
    each — the dominant pack cost at high lane counts) and one flat
    fancy-index assignment scatters all lanes at once.
    """
    with _obs_phase("pack"):
        b = len(graphs)
        sized = [as_request(item) for item in graphs]
        nn = np.fromiter((g.num_nodes for g in sized), np.int32, count=b)
        ne = np.fromiter((g.num_edges for g in sized), np.int32, count=b)
        for i, g in enumerate(sized):
            if g.num_edges > padded_edges or g.num_nodes > padded_nodes:
                raise ValueError(
                    f"graph {i} ({g.num_nodes}V/{g.num_edges}E) exceeds "
                    f"bucket ({padded_nodes}V/{padded_edges}E)")
        src = np.zeros((b, padded_edges), np.int32)
        dst = np.zeros((b, padded_edges), np.int32)
        weight = np.full((b, padded_edges), np.inf, np.float32)
        total = int(ne.sum())
        if total:
            host = jax.device_get([(g.src, g.dst, g.weight) for g in sized])
            # (lane, col) of every real edge across the batch: lane i
            # occupies cols [0, ne[i]).
            rows = np.repeat(np.arange(b), ne)
            cols = (np.arange(total, dtype=np.int64)
                    - np.repeat(np.cumsum(ne) - ne, ne))
            src[rows, cols] = np.concatenate([h[0] for h in host])
            dst[rows, cols] = np.concatenate([h[1] for h in host])
            weight[rows, cols] = np.concatenate([h[2] for h in host])
        return BatchedGraph(jnp.asarray(src), jnp.asarray(dst),
                            jnp.asarray(weight), jnp.asarray(nn),
                            jnp.asarray(ne))


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "variant", "track_covered",
                     "max_lock_waves", "compaction", "contraction"))
def batched_msf(batch: BatchedGraph, *, num_nodes: int,
                variant: str = "cas", track_covered: bool = True,
                max_lock_waves: int = 16,
                compaction: int = 0,
                contraction: bool = False) -> BatchedMSTResult:
    """Borůvka MSF over every lane of ``batch`` in one jitted while_loop.

    Args:
      batch: padded (B, E_pad) graphs; see module docstring for the padding
        contract (``pack_padded`` / ``pack_graphs`` construct it).
      num_nodes: padded per-lane vertex count V_pad (static).
      variant: "cas" or "lock" — same paper variants as the single engine;
        the lock-variant's retry-wave while_loop batches via lax select
        masking, so fast lanes idle while contended lanes drain.
      compaction: 0 = off; k > 0 = every k rounds each lane stable-
        partitions its live edges to a prefix (per-lane live counts; pad
        and finished lanes compact to empty prefixes of sentinel lanes) and
        the scan shrinks to one pow2 bucket of the *max* live count across
        lanes — the bucket switch must sit outside the vmap, so the batch
        scans at the pace of its liveliest lane.
      contraction: contract-Borůvka (DESIGN.md §2c): per-lane relabeling
        of surviving supervertices to dense ids at each epoch boundary,
        with the vertex bucket picked from the batch-max supervertex count
        OUTSIDE the vmap (mirroring the edge buckets).  Pad vertices are
        excluded from the active range up front, so padded lanes solve at
        true-size vertex buckets from the first epoch.  Requires
        ``compaction > 0``.

    Returns per-lane results; lane i is only meaningful up to
    ``batch.num_nodes[i]`` / ``batch.num_edges[i]``.
    """
    validate_variant(variant)
    if compaction and not track_covered:
        raise ValueError("compaction requires track_covered=True "
                         "(the covered bit IS the live/dead partition key)")
    if contraction and not compaction:
        raise ValueError("contraction requires compaction > 0 "
                         "(contraction happens at epoch boundaries)")
    e_pad = batch.src.shape[1]
    rank, order = jax.vmap(rank_edges)(batch.weight)

    def one_lane_init(_):
        return _init_state(num_nodes, e_pad, e_pad,
                           commit_slots=variant == "cas")

    init = jax.vmap(one_lane_init)(batch.num_nodes)

    if contraction:
        return _finish_contracted(
            batch, _contracted_loop(
                batch, rank, order, init, num_nodes=num_nodes,
                variant=variant, max_lock_waves=max_lock_waves,
                compaction=compaction),
            num_nodes=num_nodes)

    round_fn = jax.vmap(
        functools.partial(boruvka_round, variant=variant,
                          track_covered=track_covered, num_nodes=num_nodes,
                          max_lock_waves=max_lock_waves))

    if not compaction:
        def cond(s):
            return ~jnp.all(s.done)

        def body(s):
            return round_fn(s, batch.src, batch.dst, rank,
                            batch.src, batch.dst, order)

        final = jax.lax.while_loop(cond, body, init)
    else:
        sizes = scan_bucket_sizes(e_pad)

        def cond(carry):
            return ~jnp.all(carry[0].done)

        def body(carry):
            s, f = carry
            return boruvka_epoch(s, f, batch.src, batch.dst, order,
                                 round_fn=round_fn, sizes=sizes,
                                 compaction=compaction)

        final, _ = jax.lax.while_loop(
            cond, body, (init, init_frontier(batch.src, batch.dst, rank)))

    with jax.named_scope("mst.finish"):
        final = jax.vmap(materialize_commits)(final)
        total = jnp.sum(jnp.where(final.mst_mask, batch.weight, 0.0),
                        axis=1)
        comp = jax.vmap(count_components)(final.parent)
        pad_singletons = jnp.int32(num_nodes) - batch.num_nodes
        return BatchedMSTResult(
            parent=final.parent,
            mst_mask=final.mst_mask,
            num_rounds=final.num_rounds,
            num_waves=final.num_waves,
            total_weight=total,
            num_components=comp - pad_singletons,
        )


def _contracted_loop(batch: BatchedGraph, rank, order, init, *,
                     num_nodes: int, variant: str, max_lock_waves: int,
                     compaction: int) -> ContractCarry:
    """Contract-Borůvka while_loop over every lane (DESIGN.md §2c).

    ``num_active`` starts at each lane's TRUE vertex count: pad vertices
    are edge-free identity roots, so excluding them from the active range
    up front simply drops them at the first contraction (their root_map
    entries go to the sentinel and nothing ever reads them back), and the
    batch-max vertex bucket tracks real supervertices — a heavily padded
    lane runs vertex-sized work at its true size from epoch one instead
    of paying V_pad forever.
    """
    e_pad = batch.src.shape[1]
    e_sizes = scan_bucket_sizes(e_pad)
    v_sizes = vertex_bucket_sizes(num_nodes)

    def round_factory(sz_v):
        return jax.vmap(
            functools.partial(boruvka_round, variant=variant,
                              track_covered=True, num_nodes=sz_v,
                              max_lock_waves=max_lock_waves))

    def cond(c):
        return ~jnp.all(c.state.done)

    def body(c):
        return boruvka_contract_epoch(
            c, batch.src, batch.dst, order, round_factory=round_factory,
            e_sizes=e_sizes, v_sizes=v_sizes, compaction=compaction,
            e_full=e_pad)

    b = batch.src.shape[0]
    return jax.lax.while_loop(cond, body, ContractCarry(
        state=init,
        frontier=init_frontier(batch.src, batch.dst, rank),
        root_map=jnp.broadcast_to(jnp.arange(num_nodes, dtype=jnp.int32),
                                  (b, num_nodes)),
        num_active=batch.num_nodes.astype(jnp.int32)))


@jax.named_scope("mst.finish")
def _finish_contracted(batch: BatchedGraph, fin: ContractCarry, *,
                       num_nodes: int) -> BatchedMSTResult:
    """Per-lane original-id reconstruction from the root-translation table.

    Pad vertices were dropped from the active range at the first
    contraction, so their ``root_map`` entries are stale — mask them to
    segment 0 for the representative reduction (pad indices sort after
    every real vertex, so they can't win a min) and report them as
    identity singletons, matching the padding contract.  ``num_active``
    already counts exactly the real components per lane.
    """
    final = jax.vmap(materialize_commits)(fin.state)
    total = jnp.sum(jnp.where(final.mst_mask, batch.weight, 0.0), axis=1)
    iota_v = jnp.arange(num_nodes, dtype=jnp.int32)
    valid = iota_v[None, :] < batch.num_nodes[:, None]
    comp = jnp.where(valid, fin.root_map, 0)
    parent = jax.vmap(contracted_parent_original_ids,
                      in_axes=(0, None))(comp, num_nodes)
    parent = jnp.where(valid, parent, iota_v[None, :])
    return BatchedMSTResult(
        parent=parent,
        mst_mask=final.mst_mask,
        num_rounds=final.num_rounds,
        num_waves=final.num_waves,
        total_weight=total,
        num_components=fin.num_active,
    )


def unpack_lane(batch: BatchedGraph, result: BatchedMSTResult, lane: int):
    """Trim lane ``lane`` to its true sizes: (mst_mask (E,), parent (V,),
    total_weight, num_components, num_rounds).

    One-lane convenience; bulk consumers (``graphs.batching
    .unpack_results``) transfer the whole result once instead.
    """
    v = int(batch.num_nodes[lane])
    e = int(batch.num_edges[lane])
    return (np.asarray(result.mst_mask[lane])[:e],
            np.asarray(result.parent[lane])[:v],
            float(result.total_weight[lane]),
            int(result.num_components[lane]),
            int(result.num_rounds[lane]))
