"""Parallel Borůvka MST — TPU-native adaptation of Durbhakula (2020).

The paper parallelizes Borůvka on a shared-memory multicore with two
synchronization schemes for ``UnionOfComponents``:

  * **lock-variant**  - acquire lock variables on both components, re-verify,
    then merge (paper §2.2.1);
  * **CAS-variant**   - a single atomic compare-and-swap on the parent pointer
    of the absorbed component (paper §2.2.2).

TPUs have no cross-core CAS or locks, so we map the *insights* onto SPMD
dataflow (see DESIGN.md §2):

  * per-thread min-edge search            -> ``segment_min`` over packed ranks
  * thread-set merge of candidates        -> min-all-reduce (distributed_mst)
  * lock acquire / re-verify / commit     -> two-phase *propose-verify* hooking
    (merges form a matching per round - exactly what holding both locks gives)
  * CAS one-shot parent swap              -> one-phase scatter hooking with
    deterministic 2-cycle breaking (chain merges allowed, like racing CASes
    that all succeed on distinct parents)
  * the "covered" edge bit (opt-seq §2.1) -> edge masking + compaction

Distinct weights are a paper *assumption*; we make them a *construction*:
edges are ranked once by ``(weight, edge_id)`` lexicographic order and every
comparison uses the dense int32 rank.  MSTs depend only on weight order, so
this is exact, deterministic, and also fixes the duplicate-weight case.

Index spaces: the per-round *scan* arrays (``scan_src/scan_dst/scan_rank``)
may be a compacted subset of the edge list (opt-seq), but ranks are global,
so candidate resolution always goes through the full-size ``order`` /
``full_src`` / ``full_dst`` arrays and commits into the full-size MST mask.

The per-round building blocks live in :mod:`repro.core.engine` (shared by
the batched, distributed, and shard-local-topology engines); this module
holds the single-device drivers and re-exports the blocks for backward
compatibility.
"""
from __future__ import annotations

import bisect
import functools
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import annotate
from repro.core.types import Graph, MSTResult, INT_SENTINEL, ensure_sized
from repro.core.engine import (  # noqa: F401  (re-exported API)
    BoruvkaState,
    ContractCarry,
    Frontier,
    boruvka_contract_epoch,
    boruvka_epoch,
    boruvka_round,
    contract_epoch_host,
    contract_slice_host,
    contracted_parent_original_ids,
    candidate_min_edges,
    commit_edges,
    compact_frontier,
    finish_result,
    hook_cas,
    hook_lock_waves,
    init_frontier,
    init_state,
    materialize_commits,
    partner_components,
    rank_edges,
    rank_edges_host,
    resolve_candidates,
    scan_bucket_sizes,
    validate_variant,
    vertex_bucket_sizes,
)

# Backward-compatible aliases (pre-engine-extraction names).
_init_state = init_state
_finish = finish_result


# ---------------------------------------------------------------------------
# Single-device engines.
# ---------------------------------------------------------------------------

def minimum_spanning_forest(graph: Graph, *, num_nodes: Optional[int] = None,
                            variant: str = "cas",
                            track_covered: bool = True,
                            max_lock_waves: int = 16,
                            compaction: int = 0,
                            compaction_kernel: bool = False,
                            contraction: bool = False) -> MSTResult:
    """Full Borůvka MSF as a single jitted ``lax.while_loop``.

    Where the solve runs on an accelerator, the (weight, edge_id) rank is
    computed inside the jitted program (``rank_edges``, device phase
    ``mst.sort``), so the device never waits on the host for it.  On the
    CPU it is computed host-side by ``rank_edges_host`` (numpy's stable
    argsort is several times faster than XLA's CPU sort there).  Both are
    the same stable ascending sort: the forest is the same bit for bit.

    Args:
      graph: edge-list graph (static shapes), preferably sized
        (``Graph(..., num_nodes=V)``).
      num_nodes: V (static); only needed for legacy unsized graphs.
      variant: "cas" (one-phase scatter hooking, paper §2.2.2) or
               "lock" (two-phase propose-verify matching, paper §2.2.1).
      track_covered: keep the paper's ``covered`` bit so later rounds mask
               finished edges (§2.1 optimization); False = unoptimized
               baseline that re-derives everything per round.
      compaction: 0 = off; k > 0 = every k rounds, stable-partition the
               live edges to a prefix and scan only a pow2-bucketed prefix
               from then on (frontier compaction, DESIGN.md §2b).  The
               candidate/hook/commit decisions are bit-identical to the
               uncompacted engine — only the scan cost changes.
      compaction_kernel: route the live-prefix permutation through the
               Pallas stream-compaction kernel (``kernels/compact_edges``)
               instead of the jnp cumsum path.
      contraction: contract-Borůvka (DESIGN.md §2c): at every epoch
               boundary the surviving supervertices are relabeled to a
               dense ``[0, V')`` range and live endpoints rewritten, so
               later rounds also shrink the *vertex*-sized per-round work
               (segment_min, hooking, pointer jumping) — the part frontier
               compaction alone cannot touch, and what the dense classes
               need.  Requires ``compaction > 0`` (the epoch cadence is
               shared).  Hooking decisions stay bit-identical: the relabel
               is monotone, so rounds/waves/mst_mask match the
               uncontracted engines; the reported ``parent`` is the
               min-original-vertex canonical labeling.
    """
    graph = ensure_sized(graph, num_nodes)
    validate_variant(variant)
    if contraction and not compaction:
        raise ValueError("contraction requires compaction > 0 "
                         "(contraction happens at epoch boundaries)")
    rank, order = _host_ranking(graph.weight)
    if contraction:
        if rank is None:
            rank, order = _rank_edges_jit(graph.weight)
        return _contracted_host_loop(
            graph, rank, order, variant=variant,
            max_lock_waves=max_lock_waves, compaction=compaction,
            compaction_kernel=compaction_kernel)
    return _msf_jit(graph, rank, order, num_nodes=graph.num_nodes,
                    variant=variant, track_covered=track_covered,
                    max_lock_waves=max_lock_waves, compaction=compaction,
                    compaction_kernel=compaction_kernel)


def _platform() -> str:
    """The platform the solve runs on (``cpu``, ``tpu``, ``gpu``)."""
    return jax.default_backend()


def _host_ranking(weight):
    """``rank_edges_host(weight)`` where the host rank pays, else
    ``(None, None)``: the engine then ranks inside its jitted program.

    numpy's stable argsort beats XLA's CPU sort several times over, so
    the CPU keeps the host rank.  On an accelerator the host rank leaves
    the device idle for the whole sort, and copies rank and order to it
    besides; there the device sorts, in the same program as the rounds.
    """
    if _platform() == "cpu":
        return rank_edges_host(weight)
    return None, None


_rank_edges_jit = jax.jit(rank_edges)


def _bucket_cover(sizes, count: int) -> int:
    """Smallest static bucket covering ``count`` (host-side
    ``scan_bucket_index``)."""
    return sizes[bisect.bisect_left(sizes, max(count, 1))]


def _contracted_host_loop(graph: Graph, rank, order, *, variant: str,
                          max_lock_waves: int, compaction: int,
                          compaction_kernel: bool) -> MSTResult:
    """Contract-Borůvka driver: HOST epoch loop over truly-shrinking
    buffers (DESIGN.md §2c).

    Each epoch is one ``contract_epoch_host`` call whose buffer shapes ARE
    the current (edge bucket, vertex bucket) pair — the host reads back
    the post-epoch live-edge and supervertex counts, picks the next pow2
    pair, and ``contract_slice_host`` materializes the smaller buffers.
    Compared to the batched engine's in-jit ``boruvka_contract_epoch``
    (full-width while_loop carry + a ``lax.switch`` over the bucket-pair
    product), this keeps every epoch-boundary op at prefix width and
    compiles one specialization per visited pair instead of the full
    product — the same host-bucket idiom as ``_python_loop``'s opt-seq
    path, at a cost of one device round-trip per epoch (~log V of them).
    """
    num_nodes = graph.num_nodes
    e_full = graph.num_edges
    e_sizes = scan_bucket_sizes(e_full)
    v_sizes = vertex_bucket_sizes(num_nodes)
    cas = variant == "cas"

    src, dst, rk = graph.src, graph.dst, rank
    # The decode table shrinks with the edge bucket: every epoch boundary
    # re-spreads the surviving ranks to a dense prefix (engine.
    # respread_ranks), so `order_tbl` stays exactly bucket-sized and the
    # dedup pair table keeps dense rank keys across repeated contractions.
    order_tbl = order
    parent = jnp.arange(num_nodes, dtype=jnp.int32)
    covered = jnp.zeros((e_full,), bool)
    committed = (jnp.full((num_nodes,), e_full, jnp.int32) if cas else None)
    mst_mask = jnp.zeros((e_full,), bool)
    num_rounds = jnp.zeros((), jnp.int32)
    num_waves = jnp.zeros((), jnp.int32)
    root_map = jnp.arange(num_nodes, dtype=jnp.int32)
    num_active = jnp.asarray(num_nodes, jnp.int32)

    epochs = 0
    while True:
        with annotate("contract_epoch"):
            # The epoch's pack already reflects the fused multi-edge dedup
            # (engine.contract_epoch_host): once the O(V'^2) pair bound
            # fits the dense pair table, only the min-rank edge per
            # supervertex pair stays live — on dense classes this is what
            # finally lets the edge bucket collapse.
            (done, num_rounds, num_waves, mst_mask, nsrc, ndst, perm,
             live, root_map, num_active) = contract_epoch_host(
                parent, covered, committed, mst_mask, num_rounds, num_waves,
                src, dst, rk, graph.src, graph.dst, order_tbl, root_map,
                num_active, variant=variant, max_lock_waves=max_lock_waves,
                compaction=compaction, use_kernel=compaction_kernel)
        if bool(done):
            break
        epochs += 1
        if epochs > num_nodes:  # safety: can't exceed V epochs
            raise RuntimeError("contract-Borůvka failed to converge")
        n_active = int(num_active)
        new_e = _bucket_cover(e_sizes, int(live))
        new_v = _bucket_cover(v_sizes, n_active)
        src, dst, rk, order_tbl, parent, covered, slots = \
            contract_slice_host(nsrc, ndst, rk, order_tbl, perm, live,
                                new_e=new_e, new_v=new_v, e_full=e_full)
        committed = slots if cas else None

    total = jnp.sum(jnp.where(mst_mask, graph.weight, 0.0))
    return MSTResult(
        parent=contracted_parent_original_ids(root_map, num_nodes),
        mst_mask=mst_mask,
        num_rounds=num_rounds,
        num_waves=num_waves,
        total_weight=total,
        # Every surviving supervertex IS a component (done components keep
        # their dense id), so V' is the component count.
        num_components=num_active)


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "variant", "track_covered",
                     "max_lock_waves", "compaction", "compaction_kernel"))
def _msf_jit(graph: Graph, rank, order, *, num_nodes: int, variant: str,
             track_covered: bool, max_lock_waves: int, compaction: int,
             compaction_kernel: bool) -> MSTResult:
    if rank is None:  # ranked here, on the device (``_host_ranking``)
        rank, order = rank_edges(graph.weight)
    e = graph.num_edges
    init = init_state(num_nodes, e, e, commit_slots=variant == "cas")

    if not compaction:
        def cond(s):
            return ~s.done

        def body(s):
            return boruvka_round(s, graph.src, graph.dst, rank,
                                 graph.src, graph.dst, order,
                                 variant=variant,
                                 track_covered=track_covered,
                                 num_nodes=num_nodes,
                                 max_lock_waves=max_lock_waves)

        final = materialize_commits(jax.lax.while_loop(cond, body, init))
        return finish_result(graph, final, final.num_rounds)

    if not track_covered:
        raise ValueError("compaction requires track_covered=True "
                         "(the covered bit IS the live/dead partition key)")
    sizes = scan_bucket_sizes(e)
    round_fn = functools.partial(boruvka_round, variant=variant,
                                 track_covered=True, num_nodes=num_nodes,
                                 max_lock_waves=max_lock_waves)

    def cond(carry):
        return ~carry[0].done

    def body(carry):
        s, f = carry
        return boruvka_epoch(s, f, graph.src, graph.dst, order,
                             round_fn=round_fn, sizes=sizes,
                             compaction=compaction,
                             use_kernel=compaction_kernel)

    final, _ = jax.lax.while_loop(
        cond, body, (init, init_frontier(graph.src, graph.dst, rank)))
    final = materialize_commits(final)
    return finish_result(graph, final, final.num_rounds)


# The previous round's state buffers are dead the moment the next round
# returns — donating them lets XLA update parent/mask/covered in place
# across the host-side round loop (the in-jit engines get the same reuse
# for free from the while_loop carry).
@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("num_nodes", "variant", "track_covered"))
def _one_round_jit(state, scan_src, scan_dst, scan_rank, full_src, full_dst,
                   order, *, num_nodes, variant, track_covered):
    return boruvka_round(state, scan_src, scan_dst, scan_rank,
                         full_src, full_dst, order, variant=variant,
                         track_covered=track_covered, num_nodes=num_nodes)


class RoundTrace(NamedTuple):
    """Per-round observables from the instrumented host round loop.

    Lists are indexed by completed (non-final) round, matching
    ``live_edge_trace``'s historical convention: entry ``r`` is the value
    *after* round ``r+1`` ran; the terminating round (where ``done``
    flips) contributes no entry.
    """

    live: List[int]     # live (non-covered) edges after the round
    commits: List[int]  # cumulative committed MST edges after the round
    waves: List[int]    # cumulative hook waves after the round


def round_trace(graph: Graph, num_nodes: Optional[int] = None, *,
                variant: str = "cas") -> RoundTrace:
    """Round-level solve observables: live edges, cumulative commits,
    cumulative hook waves per round.

    Host-side instrumented round loop over the shared ``boruvka_round``
    block (full-width scans; only scalars are read out per round).  The
    conformance matrix pins hooking decisions — and with them rounds,
    waves and the covered bits — identical across every engine and every
    compaction cadence, so this one loop is the round-detail source for
    all of them (``MSTSolver.trace_solve`` attaches it to a
    :class:`repro.obs.SolveTrace`).
    """
    graph = ensure_sized(graph, num_nodes)
    num_nodes = graph.num_nodes
    validate_variant(variant)
    rank, order = rank_edges_host(graph.weight)
    e = graph.num_edges
    state = init_state(num_nodes, e, e)
    live, commits, waves = [], [], []
    while True:
        with annotate("boruvka_round"):
            state = _one_round_jit(state, graph.src, graph.dst, rank,
                                   graph.src, graph.dst, order,
                                   num_nodes=num_nodes, variant=variant,
                                   track_covered=True)
        if bool(state.done):
            break
        live.append(int(jnp.sum(~state.covered)))
        commits.append(int(jnp.sum(state.mst_mask)))
        waves.append(int(state.num_waves))
        if len(live) > num_nodes:
            # A correct solve needs <= log2(V) rounds (components at least
            # halve); V rounds means the hooking is cycling, and the live
            # tail is the diagnostic — a flat tail = stuck components, a
            # shrinking tail = runaway accounting.
            raise RuntimeError(
                f"Borůvka failed to converge: {len(live)} rounds exceed "
                f"num_nodes={num_nodes} (variant={variant!r}); "
                f"live edges over the last rounds: {live[-5:]}")
    return RoundTrace(live, commits, waves)


def live_edge_trace(graph: Graph, num_nodes: Optional[int] = None, *,
                    variant: str = "cas") -> list:
    """Per-round live (non-covered) edge counts — the frontier-decay signal.

    The counts are what a compacting engine's bucketed prefix tracks, so
    this is both the EXPERIMENTS.md decay table and the monotonicity
    oracle for the hypothesis property test.  (A view over
    :func:`round_trace`, which also reads commits and waves.)
    """
    return round_trace(graph, num_nodes, variant=variant).live


def mst_unoptimized(graph: Graph, num_nodes: Optional[int] = None,
                    variant: str = "cas", *, ranking=None) -> MSTResult:
    """Paper §2.1 sequential Borůvka: every round rescans *all* edges.

    ``ranking`` optionally passes a precomputed ``rank_edges_host`` result
    so A/B harnesses (fig1) can hoist the common host sort out of the
    timed region — it is identical work on both arms and only dilutes the
    measured scan-path ratio.
    """
    return _python_loop(graph, num_nodes, variant=variant, compact=False,
                        ranking=ranking)


def mst_optimized(graph: Graph, num_nodes: Optional[int] = None,
                  variant: str = "cas", *, ranking=None) -> MSTResult:
    """Paper §2.1 optimized sequential: covered edges are skipped, realized
    vectorized as compaction - masking alone saves no vector work; dropping
    lanes does."""
    return _python_loop(graph, num_nodes, variant=variant, compact=True,
                        ranking=ranking)


def _python_loop(graph: Graph, num_nodes, *, variant: str,
                 compact: bool, ranking=None) -> MSTResult:
    graph = ensure_sized(graph, num_nodes)
    num_nodes = graph.num_nodes
    validate_variant(variant)
    rank, order = ranking if ranking is not None \
        else rank_edges_host(graph.weight)
    e_full = graph.num_edges
    state = init_state(num_nodes, e_full, e_full)
    scan_src, scan_dst, scan_rank = graph.src, graph.dst, rank
    rounds = 0
    while True:
        with annotate("boruvka_round"):
            state = _one_round_jit(state, scan_src, scan_dst, scan_rank,
                                   graph.src, graph.dst, order,
                                   num_nodes=num_nodes, variant=variant,
                                   track_covered=compact)
        if bool(state.done):
            break
        rounds += 1
        if rounds > num_nodes:  # safety: can't exceed V rounds
            raise RuntimeError("Borůvka failed to converge")
        if compact:
            keep = ~state.covered
            n_keep = int(jnp.sum(keep))
            if n_keep == 0:
                break
            # Pad surviving edges to the next power of two: bounds the number
            # of distinct jit shapes to log2(E) while shrinking real work.
            bucket = min(scan_rank.shape[0],
                         max(64, 1 << (n_keep - 1).bit_length()))
            if bucket < scan_rank.shape[0]:
                idx = jnp.nonzero(keep, size=bucket, fill_value=0)[0]
                pad = jnp.arange(bucket) >= n_keep
                scan_src = scan_src[idx]
                scan_dst = scan_dst[idx]
                scan_rank = jnp.where(pad, INT_SENTINEL, scan_rank[idx])
                state = state._replace(
                    covered=jnp.where(pad, True,
                                      jnp.zeros((bucket,), bool)))
    return finish_result(graph, state, rounds)
