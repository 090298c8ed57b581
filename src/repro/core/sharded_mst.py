"""Shard-local-topology Borůvka MST — nothing bigger than (V,) ever moves.

``distributed_msf`` shards the edge *scan* but replicates the topology
(``src/dst/order``) on every device, exactly like the paper's shared edge
array — its own docstring names the scaling fix: all-gather only (V,)-sized
candidate arrays, never move topology.  This engine is that step (the
sharding move of Sanders & Schimek 2023 and the sparse-kernel formulation
of Baer et al. 2021):

  * each device owns ONE edge shard's ``src/dst/rank`` tables
    (``graphs/partition_edges.py``) and its slice of the MST mask — there
    is no replicated ``order``/``full_src``/``full_dst`` anywhere;
  * per round, candidate search is a shard-local ``segment_min`` over the
    shard's global ranks, merged by a (V,)-sized ``pmin`` all-reduce (the
    same collective as ``distributed_msf``);
  * the *owner-decode* step replaces ``resolve_candidates``: the winning
    rank is globally unique and every edge lives on exactly one shard, so
    the owning shard is the only one able to decode rank -> edge.  It
    contributes the ``(edge_id, src, dst)`` triple for each component it
    won; everyone else contributes INT_SENTINEL; a second (3, V)-sized
    ``pmin`` broadcasts the decoded triples to all shards
    (DESIGN.md §2a has the diagram);
  * hooking runs replicated on the decoded endpoints (``hook_cas`` /
    ``hook_lock_waves`` are endpoint-based, see ``core/engine.py``), and
    each shard commits only the winning edges whose ids fall inside its
    contiguous block — the MST mask stays sharded until one final gather
    (the ``out_specs`` concatenation).

Per-device memory is O(E/S + V): the edge tables shrink with the mesh while
the per-round collectives stay (V,)-sized — weak scaling in the edge
dimension (EXPERIMENTS.md §Sharded).  Edge weights never reach the devices
at all: ranks replace them in-engine, and ``total_weight`` is a host-side
reduction over the gathered mask.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import Graph, MSTResult, INT_SENTINEL, ensure_sized
from repro.core.engine import (
    BoruvkaState,
    Frontier,
    hook_cas,
    hook_lock_waves,
    make_scan_branches,
    maybe_pack_frontier,
    partner_components,
    scan_bucket_index,
    scan_bucket_sizes,
    validate_variant,
)
from repro.core.union_find import pointer_jump, count_components
from repro.graphs.partition_edges import (EdgePartition, flatten_partition,
                                          partition_edges)
from repro.obs.trace import annotate

# Re-exported so engine users have one import surface.
from repro.core.distributed_mst import make_flat_mesh  # noqa: F401


def shard_topology(part: EdgePartition, mesh: Mesh, axis: str = "data"):
    """Place the flat topology tables on the mesh, one shard row per device.

    Returns (src, dst, rank, edge_id) as (E_pad,)-shaped arrays committed to
    ``NamedSharding(mesh, P(axis))`` — each device materializes only its
    (E_shard,) block.  Tests assert on exactly this sharding spec; the
    engine consumes the arrays as-is (no reshard on entry).
    """
    if part.num_shards != mesh.shape[axis]:
        raise ValueError(f"partition has {part.num_shards} shards, mesh axis "
                         f"{axis!r} has {mesh.shape[axis]} devices")
    sharding = NamedSharding(mesh, P(axis))
    return tuple(jax.device_put(x, sharding) for x in
                 flatten_partition(part))


def sharded_msf(graph: Graph, *, num_nodes: Optional[int] = None, mesh: Mesh,
                axis: str = "data", variant: str = "cas",
                max_lock_waves: int = 16,
                partition: Optional[EdgePartition] = None,
                compaction: int = 0) -> MSTResult:
    """Minimum spanning forest with topology sharded over ``mesh[axis]``.

    Args:
      graph: edge-list graph; only used host-side (partitioning + the final
        ``total_weight`` reduction) — topology reaches devices pre-sharded.
      num_nodes: V (static).
      mesh: 1-D (or effectively 1-D over ``axis``) device mesh.
      variant: "cas" or "lock" — the paper's hooking schemes.
      partition: optional precomputed ``partition_edges(graph, n_shards)``
        (e.g. when the caller already asserted its sharding layout).
      compaction: 0 = off; k > 0 = shard-local frontier compaction every k
        rounds.  Each device stable-partitions its own shard's live edges
        (the global edge id rides along in the frontier so owner-decode and
        the contiguous-block commit survive the permutation) and both
        shard-local scans — candidate search AND owner-decode — run over a
        pow2-bucketed prefix, so per-device scan cost drops to
        O(E_live/S).  The (V,)-sized collectives are untouched.

    Returns replicated outputs identical to the single-device engine.
    """
    graph = ensure_sized(graph, num_nodes)
    num_nodes = graph.num_nodes
    validate_variant(variant)
    n_shards = mesh.shape[axis]
    e = graph.num_edges
    part = partition if partition is not None else partition_edges(
        graph, n_shards)
    if part.num_shards != n_shards:
        raise ValueError(f"partition shards ({part.num_shards}) != mesh "
                         f"axis size ({n_shards})")
    e_shard = part.shard_edges
    s_src, s_dst, s_rank, s_gid = shard_topology(part, mesh, axis)

    shard = P(axis)
    repl = P()

    def run(s_src, s_dst, s_rank, s_gid):
        shard_id = jax.lax.axis_index(axis)
        shard_start = shard_id * e_shard

        def local_commit(mask, cand_edge, commit):
            """Scatter winning GLOBAL edge ids into this shard's mask slice.

            Non-owned ids map outside [0, E_shard) and drop — each commit
            lands on exactly one shard (contiguous-block ownership).
            """
            local = cand_edge - shard_start
            ok = commit & (local >= 0) & (local < e_shard)
            idx = jnp.where(ok, local, e_shard)
            return mask.at[idx].set(True, mode="drop")

        init = BoruvkaState(
            parent=jnp.arange(num_nodes, dtype=jnp.int32),
            mst_mask=jnp.zeros((e_shard,), bool),      # local slice
            covered=jnp.zeros((e_shard,), bool),       # local slice
            num_rounds=jnp.zeros((), jnp.int32),
            num_waves=jnp.zeros((), jnp.int32),
            done=jnp.zeros((), bool),
            # CAS commit slots hold GLOBAL edge ids; INT_SENTINEL is the
            # null (outside every shard's contiguous block, unlike E,
            # which pads into the LAST shard's range).
            committed=(jnp.full((num_nodes,), INT_SENTINEL, jnp.int32)
                       if variant == "cas" else None),
        )
        # The frontier carries the global edge id alongside src/dst/rank:
        # scan slots stop being identified by position once compaction
        # permutes them, and owner-decode + the contiguous-block commit
        # both speak global ids.
        init_f = Frontier(s_src, s_dst, s_rank,
                          jnp.full((), e_shard, jnp.int32), s_gid)
        sizes = scan_bucket_sizes(e_shard) if compaction else (e_shard,)

        def decode_branch(sz):
            @jax.named_scope("mst.scan")
            def decode(ops):
                # Owner-decode over the same prefix: the cheap gathers are
                # recomputed (branch outputs must be shape-identical, so a
                # prefix-sized key can't cross the pmin between switches).
                parent, covered, f, best = ops
                cu_e = parent[f.src[:sz]]
                cv_e = parent[f.dst[:sz]]
                key = jnp.where(covered[:sz], INT_SENTINEL, f.rank[:sz])
                eidx = jnp.arange(sz, dtype=jnp.int32)
                live = key < INT_SENTINEL
                win_u = jnp.where(live & (key == best[cu_e]), eidx,
                                  INT_SENTINEL)
                win_v = jnp.where(live & (key == best[cv_e]), eidx,
                                  INT_SENTINEL)
                return jnp.minimum(
                    jax.ops.segment_min(win_u, cu_e,
                                        num_segments=num_nodes),
                    jax.ops.segment_min(win_v, cv_e,
                                        num_segments=num_nodes))
            return decode

        scan_branches = make_scan_branches(sizes, num_nodes)
        decode_branches = [decode_branch(sz) for sz in sizes]

        def cond(carry):
            return ~carry[0].done

        def body(carry):
            state, f = carry
            idx = scan_bucket_index(sizes, f.live)
            # Shard-local candidate search + (V,) min-all-reduce: identical
            # collective shape to distributed_msf.
            new_covered, local_best = jax.lax.switch(
                idx, scan_branches, (state.parent, state.covered, f))
            best = jax.lax.pmin(local_best, axis)
            has = best < INT_SENTINEL

            # Owner-decode: the shard holding the rank-winning edge (ranks
            # are globally unique; each edge lives on ONE shard) recovers
            # its local slot by segment-min over slots that match best[].
            loc = jax.lax.switch(
                idx, decode_branches, (state.parent, new_covered, f, best))
            owned = loc < INT_SENTINEL
            le = jnp.clip(loc, 0, e_shard - 1)
            # (3, V) payload pmin: the second, still (V,)-sized collective
            # broadcasting (edge_id, src, dst) from the owner to everyone.
            payload = jnp.where(
                owned[None, :],
                jnp.stack([f.edge_id[le], f.src[le], f.dst[le]]),
                INT_SENTINEL)
            cand_edge, end_u, end_v = jax.lax.pmin(payload, axis)
            cand_edge = jnp.where(has, cand_edge, 0)
            end_u = jnp.where(has, end_u, 0)
            end_v = jnp.where(has, end_v, 0)

            other, iota = partner_components(state.parent, has, end_u, end_v)
            committed = state.committed
            if variant == "cas":
                new_parent, commit = hook_cas(state.parent, has, cand_edge,
                                              other, iota)
                # Write-once (V,) commit slots of GLOBAL ids; the local
                # mask is materialized once after the loop.
                mst_mask = state.mst_mask
                committed = jnp.where(commit, cand_edge, committed)
                new_parent = pointer_jump(new_parent)
                waves = jnp.ones((), jnp.int32)
            else:
                new_parent, mst_mask, waves = hook_lock_waves(
                    state.parent, state.mst_mask, has, cand_edge,
                    end_u, end_v, max_waves=max_lock_waves,
                    commit_fn=local_commit)
            done = ~jnp.any(has)
            state = BoruvkaState(
                new_parent, mst_mask, new_covered,
                state.num_rounds + jnp.where(done, 0, 1),
                state.num_waves + jnp.where(done, 0, waves), done,
                committed)
            if compaction:
                # Shard-local gated pack; devices may diverge on the gate
                # (no collectives inside).
                state, f = maybe_pack_frontier(state, f, sizes, compaction)
            return state, f

        final, _ = jax.lax.while_loop(cond, body, (init, init_f))
        if final.committed is not None:
            # One scatter per solve: every slot holding a global id inside
            # this shard's contiguous block lands in the local mask
            # (INT_SENTINEL nulls fall outside every block and drop).
            final = final._replace(mst_mask=local_commit(
                final.mst_mask, final.committed,
                jnp.ones((num_nodes,), bool)))
        ncomp = count_components(final.parent)
        return (final.parent, final.mst_mask, final.num_rounds,
                final.num_waves, ncomp)

    run_sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(shard, shard, shard, shard),
        # mst_mask stays sharded through the whole solve; out_specs P(axis)
        # is the single gather that assembles the global mask.
        out_specs=(repl, shard, repl, repl, repl), check_vma=False)
    with annotate("sharded_msf"):
        parent, mask_pad, rounds, waves, ncomp = run_sharded(
            s_src, s_dst, s_rank, s_gid)
    mst_mask = mask_pad[:e]
    # Weights never reached the devices; one host-side reduction.
    total = jnp.sum(jnp.where(mst_mask, graph.weight, 0.0))
    return MSTResult(parent=parent, mst_mask=mst_mask, num_rounds=rounds,
                     num_waves=waves, total_weight=total,
                     num_components=ncomp)
