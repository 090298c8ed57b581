"""Distributed Borůvka MST — the paper's thread parallelism as SPMD.

Paper §2.2: every thread scans *all* edges (staggered starts) and maintains
minimum[] for the vertices it owns; the union phase is synchronized.  SPMD
mapping (DESIGN.md §2):

  * thread        -> mesh device
  * edge scan     -> each device scans only its contiguous **edge shard**
                     (stronger than the paper: work is partitioned, not just
                     staggered, so there are no collisions at all)
  * minimum[]     -> per-device (V,) candidate ranks from ``segment_min``
  * owner merge   -> ``lax.pmin`` over the mesh axis: a single min-all-reduce
                     replaces all owner_tid[] bookkeeping
  * union phase   -> executed *replicated*: every device applies the same
                     deterministic hooking to its copy of parent[]

Graph topology (src/dst/order) is replicated, like the paper's shared edge
array; only scan work is partitioned.  For graphs too large to replicate,
``core/sharded_mst.py`` keeps even the topology shard-local (owner-decode
collective instead of replicated ``order``/``full_src``/``full_dst``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.types import Graph, MSTResult, INT_SENTINEL, ensure_sized
from repro.core.engine import (
    BoruvkaState,
    hook_cas,
    hook_lock_waves,
    init_frontier,
    init_state,
    make_scan_branches,
    materialize_commits,
    maybe_pack_frontier,
    rank_edges_host,
    resolve_candidates,
    scan_bucket_index,
    scan_bucket_sizes,
    validate_variant,
)
from repro.core.union_find import pointer_jump, count_components
from repro.obs.trace import annotate


def _pad_to(x, n, fill):
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])


def distributed_msf(graph: Graph, *, num_nodes: Optional[int] = None, mesh: Mesh,
                    axis: str = "data", variant: str = "cas",
                    max_lock_waves: int = 16,
                    compaction: int = 0) -> MSTResult:
    """Minimum spanning forest with edge scanning sharded over ``mesh[axis]``.

    ``compaction``: 0 = off; k > 0 = every k rounds each device stable-
    partitions its own scan shard's live edges to a prefix and scans a
    pow2-bucketed prefix afterwards.  Compaction is entirely shard-local
    (per-device live counts, no collective); the bucket switch holds no
    collectives either, so devices can sit in different buckets while the
    (V,)-sized ``pmin`` merges stay outside.

    Returns replicated outputs identical to the single-device engine.
    """
    graph = ensure_sized(graph, num_nodes)
    num_nodes = graph.num_nodes
    validate_variant(variant)
    n_shards = mesh.shape[axis]
    e = graph.num_edges
    e_pad = -(-e // n_shards) * n_shards
    rank, order = rank_edges_host(graph.weight)
    scan_src = _pad_to(graph.src, e_pad, 0)
    scan_dst = _pad_to(graph.dst, e_pad, 0)
    scan_rank = _pad_to(rank, e_pad, INT_SENTINEL)

    # All other mesh axes are unused: broadcast over them (replicated).
    shard = P(axis)
    repl = P()

    def run(s_src, s_dst, s_rank, f_src, f_dst, f_order, weight):
        e_scan = s_rank.shape[0]
        init = init_state(num_nodes, e, e_scan,
                          commit_slots=variant == "cas")
        sizes = scan_bucket_sizes(e_scan) if compaction else (e_scan,)

        branches = make_scan_branches(sizes, num_nodes)

        def cond(carry):
            return ~carry[0].done

        def body(carry):
            state, f = carry
            idx = scan_bucket_index(sizes, f.live)
            new_covered, local_best = jax.lax.switch(
                idx, branches, (state.parent, state.covered, f))
            # The paper's cross-thread merge of minimum[]: one collective.
            best = jax.lax.pmin(local_best, axis)
            has, cand_edge, end_u, end_v, other, iota = resolve_candidates(
                best, f_order, f_src, f_dst, state.parent)
            committed = state.committed
            if variant == "cas":
                new_parent, commit = hook_cas(state.parent, has, cand_edge,
                                              other, iota)
                # Write-once (V,) commit slots (see engine.BoruvkaState).
                mst_mask = state.mst_mask
                committed = jnp.where(commit, cand_edge, committed)
                new_parent = pointer_jump(new_parent)
                waves = jnp.ones((), jnp.int32)
            else:
                new_parent, mst_mask, waves = hook_lock_waves(
                    state.parent, state.mst_mask, has, cand_edge,
                    end_u, end_v, max_waves=max_lock_waves)
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

        final, _ = jax.lax.while_loop(
            cond, body, (init, init_frontier(s_src, s_dst, s_rank)))
        final = materialize_commits(final)
        total = jnp.sum(jnp.where(final.mst_mask, weight, 0.0))
        ncomp = count_components(final.parent)
        return (final.parent, final.mst_mask, final.num_rounds,
                final.num_waves, total, ncomp)

    run_sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(shard, shard, shard, repl, repl, repl, repl),
        # Outputs are replicated by the pmin merges, which the checker
        # cannot infer through the while_loop.
        out_specs=repl, check_vma=False)
    with annotate("distributed_msf"):
        parent, mst_mask, rounds, waves, total, ncomp = run_sharded(
            scan_src, scan_dst, scan_rank, graph.src, graph.dst, order,
            graph.weight)
    return MSTResult(parent=parent, mst_mask=mst_mask, num_rounds=rounds,
                     num_waves=waves, total_weight=total,
                     num_components=ncomp)


def make_flat_mesh(num_devices: Optional[int] = None,
                   axis: str = "data") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices (all of them
    when None); raises when fewer devices exist than were asked for."""
    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(f"asked for a {num_devices}-device mesh, but "
                             f"only {len(devs)} devices exist")
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis,))
