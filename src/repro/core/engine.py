"""Shared per-round Borůvka building blocks — consumed by every engine.

``core/mst.py`` (single-device + sequential baselines), ``core/batched_mst``
(vmapped multi-graph), ``core/distributed_mst`` (edge-scan sharding,
replicated topology) and ``core/sharded_mst`` (shard-local topology) are all
the same per-round dataflow wired to different memory/collective layouts:

    candidate search  ->  ``candidate_min_edges``  (segment_min over ranks)
    candidate decode  ->  ``resolve_candidates``   (rank -> edge, endpoints)
    CAS hooking       ->  ``hook_cas``             (paper §2.2.2)
    lock hooking      ->  ``hook_lock_waves``      (paper §2.2.1)
    commit            ->  ``commit_edges``         (scatter into the mask)

The blocks are layout-agnostic on purpose:

  * ``hook_lock_waves`` takes the candidate edges' *endpoint arrays*
    (``end_u``/``end_v``, both (V,)) instead of indexing a replicated
    ``full_src``/``full_dst`` — a shard-local engine decodes endpoints via
    its owner-decode collective and passes them straight in;
  * the same reason makes the commit step pluggable (``commit_fn``): the
    replicated engines scatter into a full-size (E,) mask, the sharded
    engine into its local (E_shard,) slice.

``rank_edges`` lives here too: the (weight, edge_id) dense rank is the
distinct-weights *construction* every engine builds on (see DESIGN.md §2).

Frontier compaction (DESIGN.md §2b) also lives here: after round 1 the
covered/self edges grow to dominate the scan, so every compaction-capable
engine periodically stable-partitions the live lanes to a prefix
(``compact_frontier``) and then scans only a power-of-two *bucketed prefix*
(``boruvka_epoch`` / ``scan_bucket_sizes``).  The pow2 bucketing is the
same recompile-bounding idea as ``graphs/batching.py``, applied inside a
single jitted ``while_loop`` via ``lax.switch`` over statically-sized slices.

Every block is traced under a device phase name (``jax.named_scope``,
metadata only), so a profiler trace maps each XLA op to a step of the
round whatever the engine (DESIGN.md §4): ``mst.scan`` (endpoint-label
gathers, covered mask, candidate minima: the E-sized work), ``mst.hook``
(decode, CAS hooking: V-sized), ``mst.lock`` (the lock variant's waves),
``mst.jump`` (pointer jumping), ``mst.sort`` (in-jit edge ranking),
``mst.compact`` (compaction and contraction epochs), ``mst.finish``
(commit flush, result).  The innermost scope wins: a round inside an
epoch keeps its names, a jump inside a lock wave reads ``mst.jump``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Graph, MSTResult, INT_SENTINEL
from repro.core.union_find import pointer_jump, count_components
from repro.obs.trace import phase as _obs_phase

# The paper's two synchronization schemes — the only hooking variants any
# engine implements.  Every dispatch entry validates against this tuple
# eagerly (a typo'd variant used to fail opaquely inside the round
# machinery, mid-trace).
VARIANTS = ("cas", "lock")


def validate_variant(variant: str) -> str:
    """Eagerly reject unknown hooking variants with the known set listed."""
    if variant not in VARIANTS:
        raise ValueError(
            f"unknown variant {variant!r}; known: {list(VARIANTS)}")
    return variant


# ---------------------------------------------------------------------------
# Edge ranking: "distinct weights" as a structural property.
# ---------------------------------------------------------------------------

@jax.named_scope("mst.sort")
def rank_edges(weight: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense rank of every edge under (weight, edge_id) lexicographic order.

    Returns:
      rank:  (E,) int32, rank[e] = position of edge e in the sorted order.
      order: (E,) int32, order[r] = edge id holding rank r (rank's inverse).
    """
    e = weight.shape[0]
    order = jnp.argsort(weight, stable=True).astype(jnp.int32)
    rank = jnp.zeros((e,), jnp.int32).at[order].set(
        jnp.arange(e, dtype=jnp.int32)
    )
    return rank, order


def rank_edges_host(weight) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``rank_edges`` on the host: numpy's stable argsort.

    Bit-identical ranks/order to the jnp version (both are stable ascending
    sorts, so ties break by edge id either way) but ~5-10x faster than the
    XLA CPU sort — a fixed per-solve cost worth dodging for every engine
    whose rank is computed at the host level (single, sequential,
    distributed, sharded; the batched engine ranks in-jit under vmap).
    """
    with _obs_phase("rank"):
        w = np.asarray(weight)
        e = w.shape[0]
        order = np.argsort(w, kind="stable").astype(np.int32)
        rank = np.empty((e,), np.int32)
        rank[order] = np.arange(e, dtype=np.int32)
        return jnp.asarray(rank), jnp.asarray(order)


class BoruvkaState(NamedTuple):
    parent: jnp.ndarray    # (V,) component array, fully compressed
    mst_mask: jnp.ndarray  # (E_full,) bool, committed MST edges ("M")
    covered: jnp.ndarray   # (E_scan,) bool, paper's covered bit
    num_rounds: jnp.ndarray
    num_waves: jnp.ndarray  # lock-variant retry waves (== rounds for CAS)
    done: jnp.ndarray
    # CAS-only commit accumulator: committed[c] = edge id component c
    # committed, or E_full.  A committing root is absorbed the same round
    # and never roots again, so each slot is written AT MOST ONCE — the
    # per-round commit becomes one (V,) `where` instead of a (V,)-index
    # scatter into the (E,) mask (the scatter was the single largest
    # fixed per-round cost), and `materialize_commits` scatters once at
    # the end.  None = scatter-per-round (the lock variant re-commits
    # from surviving roots, so it keeps the in-round scatter).
    committed: Optional[jnp.ndarray] = None  # (V,) int32 edge ids or None


def init_state(num_nodes: int, e_full: int, e_scan: int,
               *, commit_slots: bool = False) -> BoruvkaState:
    return BoruvkaState(
        parent=jnp.arange(num_nodes, dtype=jnp.int32),
        mst_mask=jnp.zeros((e_full,), bool),
        covered=jnp.zeros((e_scan,), bool),
        num_rounds=jnp.zeros((), jnp.int32),
        num_waves=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
        committed=(jnp.full((num_nodes,), e_full, jnp.int32)
                   if commit_slots else None),
    )


@jax.named_scope("mst.finish")
def materialize_commits(state: BoruvkaState) -> BoruvkaState:
    """Flush the (V,) CAS commit slots into the (E,) mask — one scatter
    per solve.  No-op for states without commit slots."""
    if state.committed is None:
        return state
    mask = state.mst_mask.at[state.committed].set(True, mode="drop")
    return state._replace(mst_mask=mask)


@jax.named_scope("mst.finish")
def finish_result(graph: Graph, state: BoruvkaState, rounds) -> MSTResult:
    total = jnp.sum(jnp.where(state.mst_mask, graph.weight, 0.0))
    return MSTResult(
        parent=state.parent,
        mst_mask=state.mst_mask,
        num_rounds=jnp.asarray(rounds, jnp.int32),
        num_waves=state.num_waves,
        total_weight=total,
        num_components=count_components(state.parent),
    )


# ---------------------------------------------------------------------------
# Frontier compaction: live-edge prefix + pow2 scan buckets.
# ---------------------------------------------------------------------------

MIN_SCAN_BUCKET = 64  # below this, all prefixes collapse into one tiny bucket


class Frontier(NamedTuple):
    """Permuted scan arrays with the live lanes packed into a prefix.

    ``live`` counts the non-covered lanes as of the last compaction: lanes
    ``[0, live)`` are (or were) live, everything after is covered with a
    sentinel rank, so a scan over any prefix >= ``live`` sees every live
    edge.  ``edge_id`` rides along for engines whose scan lanes are not
    identified by position (the shard-local engine's owner-decode); ``None``
    elsewhere.
    """

    src: jnp.ndarray   # (..., E_scan) int32
    dst: jnp.ndarray   # (..., E_scan) int32
    rank: jnp.ndarray  # (..., E_scan) int32, suffix lanes INT_SENTINEL
    live: jnp.ndarray  # (...,) int32 live-lane count of the packed prefix
    edge_id: Optional[jnp.ndarray] = None  # (..., E_scan) int32 or None


def init_frontier(scan_src, scan_dst, scan_rank, edge_id=None) -> Frontier:
    """Uncompacted frontier: every lane counts as live."""
    e = scan_src.shape[-1]
    live = jnp.full(scan_src.shape[:-1], e, jnp.int32)
    return Frontier(scan_src, scan_dst, scan_rank, live, edge_id)


@jax.named_scope("mst.compact")
def live_prefix_permutation(covered) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable partition of lane ids on the covered bit.

    Returns ``(perm, live)``: ``perm`` is a permutation of ``arange(E)``
    with the live (non-covered) lane ids first — both halves keep their
    original relative order, i.e. a stable sort on the covered bit — and
    ``live`` is the number of live lanes.  O(E) cumsums + one scatter, no
    argsort.  The Pallas stream-compaction kernel
    (``kernels/compact_edges``) computes the same permutation on-device.
    """
    e = covered.shape[0]
    lane = jnp.arange(e, dtype=jnp.int32)
    live = jnp.sum(~covered).astype(jnp.int32)
    pos = jnp.where(covered,
                    live + jnp.cumsum(covered) - 1,
                    jnp.cumsum(~covered) - 1).astype(jnp.int32)
    perm = jnp.zeros((e,), jnp.int32).at[pos].set(lane)
    return perm, live


@jax.named_scope("mst.compact")
def compact_frontier(frontier: Frontier, covered,
                     *, use_kernel: bool = False
                     ) -> Tuple[Frontier, jnp.ndarray]:
    """Pack the live lanes of ``frontier`` into a prefix (full width).

    Returns the permuted frontier and its new covered array (False on the
    live prefix, True after).  Suffix ranks are forced to INT_SENTINEL so a
    bucketed scan that overshoots ``live`` still can't elect a dead edge.
    ``use_kernel`` routes the permutation through the Pallas
    stream-compaction kernel instead of the jnp cumsum path.
    """
    if use_kernel:
        from repro.kernels.compact_edges.ops import compact_edges
        perm, live = compact_edges(covered)
    else:
        perm, live = live_prefix_permutation(covered)
    e = covered.shape[0]
    pad = jnp.arange(e, dtype=jnp.int32) >= live
    return Frontier(
        src=frontier.src[perm],
        dst=frontier.dst[perm],
        rank=jnp.where(pad, INT_SENTINEL, frontier.rank[perm]),
        live=live,
        edge_id=None if frontier.edge_id is None else frontier.edge_id[perm],
    ), pad


def _pack_prefix(frontier: Frontier, covered, sz: int, use_kernel: bool):
    """Pack live lanes within the first ``sz`` slots; suffix is untouched
    (the frontier invariant guarantees it is already all-dead).

    Fast path: only the LIVE lanes are scattered to their prefix slots
    (one cumsum + 3-4 drop-mode scatters).  Dead lanes keep stale values —
    harmless, because their ranks are forced to INT_SENTINEL and their
    covered bits to True, which is all the scan ever looks at.  The
    ``use_kernel`` path routes through the Pallas stream-compaction
    kernel's full stable permutation instead.
    """
    def one(src, dst, rank, eid, cov):
        sub = Frontier(src[:sz], dst[:sz], rank[:sz], jnp.int32(sz),
                       None if eid is None else eid[:sz])
        if use_kernel:
            packed, pad = compact_frontier(sub, cov[:sz], use_kernel=True)
        else:
            alive = ~cov[:sz]
            live = jnp.sum(alive).astype(jnp.int32)
            # Stable: live lanes keep their relative order in the prefix.
            pos = jnp.where(alive, jnp.cumsum(alive) - 1, sz).astype(
                jnp.int32)
            pad = jnp.arange(sz, dtype=jnp.int32) >= live

            def scatter(x):
                # Dead lanes aim at pos == sz: out of bounds for the
                # prefix-sized buffer, so drop-mode discards them.
                xp = x[:sz]
                return xp.at[pos].set(xp, mode="drop")

            packed = Frontier(
                src=scatter(src), dst=scatter(dst),
                rank=jnp.where(pad, INT_SENTINEL, scatter(rank)),
                live=live,
                edge_id=None if eid is None else scatter(eid))
        return (src.at[:sz].set(packed.src),
                dst.at[:sz].set(packed.dst),
                rank.at[:sz].set(packed.rank),
                None if eid is None else eid.at[:sz].set(packed.edge_id),
                cov.at[:sz].set(pad),
                packed.live)

    if covered.ndim == 1:
        src, dst, rank, eid, cov, live = one(
            frontier.src, frontier.dst, frontier.rank, frontier.edge_id,
            covered)
    else:
        # Batched (B, E_pad) layout: per-lane pack under one static sz.
        one_v = jax.vmap(one, in_axes=(0, 0, 0,
                                       None if frontier.edge_id is None
                                       else 0, 0))
        src, dst, rank, eid, cov, live = one_v(
            frontier.src, frontier.dst, frontier.rank, frontier.edge_id,
            covered)
    return Frontier(src, dst, rank, live, eid), cov


def compact_frontier_bucketed(frontier: Frontier, covered,
                              sizes: Tuple[int, ...],
                              *, use_kernel: bool = False
                              ) -> Tuple[Frontier, jnp.ndarray]:
    """``compact_frontier`` bounded to the current pow2 bucket.

    Everything beyond the current bucket is already packed-dead, so the
    pack pass (permutation + gathers) only needs to touch the bucket
    prefix — compaction cost shrinks along with the scan it accelerates.
    Same ``lax.switch``-over-static-sizes shape as the round itself.
    """
    def branch(sz):
        def run(ops):
            f, cov = ops
            return _pack_prefix(f, cov, sz, use_kernel)
        return run

    idx = scan_bucket_index(sizes, jnp.max(frontier.live))
    return jax.lax.switch(idx, [branch(sz) for sz in sizes],
                          (frontier, covered))


# ---------------------------------------------------------------------------
# Graph contraction: relabel supervertices to a dense range between epochs.
# ---------------------------------------------------------------------------

class ContractCarry(NamedTuple):
    """While-loop carry of the contract-Borůvka engines (DESIGN.md §2c).

    The vertex-side analogue of :class:`Frontier`: buffers stay full-width
    (static shapes), the *active* prefix shrinks.  ``root_map`` is the
    root-translation table — for every ORIGINAL vertex, the contracted id
    of its component as of the last contraction — so endpoints decoded
    from the full-size topology arrays can be translated into the current
    contracted space, and the final parent/components can be reported in
    original vertex ids.  ``num_active`` is the contracted vertex count
    V' (supervertices, including finished components: they must keep
    their dense id so ``root_map`` stays total).
    """

    state: BoruvkaState      # full-width buffers; prefixes are active
    frontier: Frontier       # full-width edge buffers, live prefix packed
    root_map: jnp.ndarray    # (..., V_orig) int32 original -> contracted id
    num_active: jnp.ndarray  # (...,) int32 contracted vertex count V'


def relabel_roots(isroot) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Monotone dense rank over the root set (jnp path).

    Root ``i`` gets ``|{j < i : isroot[j]}|``; non-roots get INT_SENTINEL
    (never read through — endpoint lookups go ``new_id[parent[x]]`` and
    ``parent[x]`` is always a root).  Monotonicity preserves the relative
    order of root ids, which is what keeps the CAS 2-cycle break and the
    lock arbitration making bit-identical decisions on the contracted
    graph.  The Pallas ``kernels/relabel_vertices`` kernel computes the
    same table on-device with a 2-phase count-then-assign grid.
    """
    isroot = isroot.astype(bool)
    rank = (jnp.cumsum(isroot, axis=-1) - 1).astype(jnp.int32)
    new_id = jnp.where(isroot, rank, INT_SENTINEL)
    return new_id, jnp.sum(isroot, axis=-1).astype(jnp.int32)


@jax.named_scope("mst.compact")
def count_active_roots(parent, num_active) -> jnp.ndarray:
    """Roots among the active id range ``[0, num_active)`` — the live
    supervertex count the vertex buckets track (buffer ids beyond
    ``num_active`` are identity-parent padding and must not count)."""
    sz = parent.shape[-1]
    iota = jnp.arange(sz, dtype=jnp.int32)
    active = iota < jnp.asarray(num_active, jnp.int32)[..., None]
    return jnp.sum((parent == iota) & active, axis=-1).astype(jnp.int32)


def _contract_prefix(state: BoruvkaState, frontier: Frontier, root_map,
                     num_active, sz_e: int, sz_v: int, e_full: int,
                     use_kernel: bool):
    """One contraction: relabel surviving roots of the ``[0, sz_v)`` prefix
    to a dense ``[0, V'')`` range, flush CAS commit slots, rewrite the
    ``[0, sz_e)`` scan lanes' endpoints through the relabeling, pack the
    live lanes, and reset the parent buffer to identity (every contracted
    supervertex is its own root).

    Lanes/slots beyond the prefixes are untouched: they are already
    packed-dead (sentinel ranks / sentinel commit slots) and the buckets
    only ever shrink, so stale suffix values are never read again.
    """
    def one(parent, covered, committed, mst_mask, src, dst, rank, eid,
            rmap, n_act):
        iota = jnp.arange(sz_v, dtype=jnp.int32)
        par = parent[:sz_v]
        isroot = (par == iota) & (iota < n_act)
        if use_kernel:
            from repro.kernels.relabel_vertices.ops import relabel_vertices
            new_id, n_new = relabel_vertices(isroot)
        else:
            new_id, n_new = relabel_roots(isroot)
        if committed is not None:
            # Commit slots are addressed by contracted id, which this
            # relabeling is about to reuse: flush them into the (E,) mask
            # now (sentinel e_full slots scatter out of bounds -> dropped)
            # and reset, restoring the write-once invariant per epoch.
            mst_mask = mst_mask.at[committed[:sz_v]].set(True, mode="drop")
            committed = committed.at[:sz_v].set(e_full)
        # Coverage refresh under the post-hook parent (the in-round covered
        # bit lags hooking by one round), fused with the endpoint rewrite:
        # cu/cv are this epoch's final component ids of each scan lane.
        cu = par[src[:sz_e]]
        cv = par[dst[:sz_e]]
        covered = covered.at[:sz_e].set(covered[:sz_e] | (cu == cv))
        # Rewrite endpoints through the relabeling; every lane's component
        # id is a root, so new_id reads never see the sentinel.
        src = src.at[:sz_e].set(new_id[cu])
        dst = dst.at[:sz_e].set(new_id[cv])
        packed, covered = _pack_prefix(
            Frontier(src, dst, rank, jnp.int32(sz_e), eid), covered, sz_e,
            use_kernel)
        # Root-translation table: original vertex -> new contracted id.
        rmap = new_id[par[rmap]]
        parent = jnp.arange(parent.shape[0], dtype=jnp.int32)
        return (parent, covered, committed, mst_mask, packed.src,
                packed.dst, packed.rank, packed.edge_id, rmap, n_new,
                packed.live)

    args = (state.parent, state.covered, state.committed, state.mst_mask,
            frontier.src, frontier.dst, frontier.rank, frontier.edge_id,
            root_map, jnp.asarray(num_active, jnp.int32))
    if state.covered.ndim == 1:
        out = one(*args)
    else:
        # Batched (B, ...) layout: per-lane contraction under one static
        # (sz_e, sz_v) pair — the bucket choice itself is batch-max and
        # sits OUTSIDE the vmap (a vmapped switch would run every branch).
        out = jax.vmap(one, in_axes=(
            0, 0, None if state.committed is None else 0, 0, 0, 0, 0,
            None if frontier.edge_id is None else 0, 0, 0))(*args)
    (parent, covered, committed, mst_mask, src, dst, rank, eid, rmap,
     n_new, live) = out
    new_state = state._replace(parent=parent, covered=covered,
                               committed=committed, mst_mask=mst_mask)
    return (new_state, Frontier(src, dst, rank, live, eid), rmap, n_new)


def vertex_bucket_sizes(num_nodes: int,
                        min_bucket: int = MIN_SCAN_BUCKET
                        ) -> Tuple[int, ...]:
    """Static pow2 vertex-prefix lengths — the vertex-side mirror of
    ``scan_bucket_sizes``."""
    return scan_bucket_sizes(num_nodes, min_bucket)


@jax.named_scope("mst.compact")
def boruvka_contract_epoch(carry: ContractCarry, full_src, full_dst, order,
                           *, round_factory,
                           e_sizes: Tuple[int, ...],
                           v_sizes: Tuple[int, ...],
                           compaction: int, e_full: int,
                           use_kernel: bool = False) -> ContractCarry:
    """One contract-Borůvka epoch: rounds at a fixed (E, V) bucket pair,
    then ONE pack + contraction (DESIGN.md §2c).

    The generalization of :func:`boruvka_epoch` to a 2-D bucket lattice:
    the ``lax.switch`` ranges over (edge bucket, vertex bucket) *pairs*,
    and the chosen branch runs rounds over the statically-sliced edge AND
    vertex prefixes until the forest completes or — checked every
    ``compaction`` rounds — either the live-edge count or the surviving
    supervertex count has dropped to a smaller bucket.  The epoch then
    relabels the surviving roots to a dense ``[0, V')`` range
    (``_contract_prefix``), so the next epoch re-enters at the shrunken
    pair and every per-round vertex-sized op (segment_min, hooking,
    pointer jumping) runs at the contracted size — the piece frontier
    compaction alone cannot shrink, and the reason the dense classes
    regressed under it.

    ``round_factory(sz_v)`` binds the round body to a static vertex count
    (``boruvka_round`` partial for the single engine, its ``jax.vmap``
    for the batched engine); the round receives ``carry.root_map`` so
    candidate endpoints decoded from the full-size topology arrays are
    translated into the contracted space.  Both bucket indices reduce
    with ``jnp.max`` over lane axes OUTSIDE any vmap.
    """
    idx_e = scan_bucket_index(e_sizes, jnp.max(carry.frontier.live))
    idx_v = scan_bucket_index(v_sizes, jnp.max(carry.num_active))
    idx = idx_e * len(v_sizes) + idx_v

    def branch(i_e, sz_e, i_v, sz_v):
        round_fn = round_factory(sz_v)

        def run(c: ContractCarry) -> ContractCarry:
            st, f, rmap, n_act = c
            src = f.src[..., :sz_e]
            dst = f.dst[..., :sz_e]
            rank = f.rank[..., :sz_e]
            sub0 = st._replace(
                parent=st.parent[..., :sz_v],
                covered=st.covered[..., :sz_e],
                committed=None if st.committed is None
                else st.committed[..., :sz_v])

            def inner_cond(ic):
                st_i, live_e, live_v = ic
                shrink = ((scan_bucket_index(e_sizes, jnp.max(live_e)) < i_e)
                          | (scan_bucket_index(v_sizes, jnp.max(live_v))
                             < i_v))
                cadence = (jnp.max(st_i.num_rounds) % compaction) == 0
                return ~jnp.all(st_i.done) & ~(cadence & shrink)

            def inner_body(ic):
                st_i, _, _ = ic
                st_i = round_fn(st_i, src, dst, rank, full_src, full_dst,
                                order, rmap)
                live_e = jnp.sum(~st_i.covered, axis=-1).astype(jnp.int32)
                live_v = count_active_roots(st_i.parent, n_act)
                return st_i, live_e, live_v

            sub, _, _ = jax.lax.while_loop(inner_cond, inner_body,
                                           (sub0, f.live, n_act))
            # Splice the prefix state back into the full-width buffers,
            # then contract: relabel + flush + endpoint rewrite + pack.
            full = st._replace(
                parent=st.parent.at[..., :sz_v].set(sub.parent),
                covered=st.covered.at[..., :sz_e].set(sub.covered),
                committed=st.committed if st.committed is None
                else st.committed.at[..., :sz_v].set(sub.committed),
                mst_mask=sub.mst_mask,
                num_rounds=sub.num_rounds, num_waves=sub.num_waves,
                done=sub.done)
            return ContractCarry(*_contract_prefix(
                full, f, rmap, n_act, sz_e, sz_v, e_full, use_kernel))
        return run

    branches = [branch(i_e, sz_e, i_v, sz_v)
                for i_e, sz_e in enumerate(e_sizes)
                for i_v, sz_v in enumerate(v_sizes)]
    return jax.lax.switch(idx, branches, carry)


@jax.named_scope("mst.compact")
def dedup_parallel_edges(cov, nsrc, ndst, rank, n_new):
    """Cover every non-minimal parallel edge between contracted endpoint
    pairs — the other half of true graph contraction, and the measured fix
    for the dense-class regression: after a few rounds V' is tiny while
    tens of thousands of live edges remain, nearly all parallel edges
    between the same supervertex pairs.  A non-minimal parallel edge can
    never be EITHER endpoint component's candidate (the kept pair-minimum
    has a smaller rank and the same endpoints), so covering them is
    invisible to the hooking decisions — rounds, waves and the committed
    edge set stay bit-identical — but it lets the edge bucket collapse
    toward the O(V'^2) pair bound.  Scatter-min over a dense pair table of
    static size ``sz_e``; the cond predicate guarantees every live pair
    key ``u * V' + v`` fits the table (and int32) — no-op until V'^2 fits.

    Shared by the contract-Borůvka epoch tail (``contract_epoch_host``)
    and the spmm engine's epoch tail (``core/spmm_mst.py``).
    """
    sz_e = cov.shape[0]

    def dedup(c):
        u = jnp.minimum(nsrc, ndst)
        v = jnp.maximum(nsrc, ndst)
        key = jnp.where(c, sz_e, u * n_new + v)  # dead lanes -> dropped
        live_rank = jnp.where(c, INT_SENTINEL, rank)
        best = jnp.full((sz_e,), INT_SENTINEL, jnp.int32).at[key].min(
            live_rank, mode="drop")
        keep = ~c & (rank == best.at[key].get(mode="fill",
                                              fill_value=INT_SENTINEL))
        return ~keep

    return jax.lax.cond(
        n_new.astype(jnp.float32) ** 2 <= jnp.float32(sz_e),
        dedup, lambda c: c, cov)


@functools.partial(
    jax.jit, static_argnames=("variant", "max_lock_waves", "compaction",
                              "use_kernel"))
@jax.named_scope("mst.compact")
def contract_epoch_host(parent, covered, committed, mst_mask, num_rounds,
                        num_waves, src, dst, rank, full_src, full_dst,
                        order, root_map, num_active, *, variant: str,
                        max_lock_waves: int, compaction: int,
                        use_kernel: bool):
    """One contract-Borůvka epoch for the HOST epoch loop (single engine).

    Unlike :func:`boruvka_contract_epoch` (the batched engine's in-jit
    variant, which must keep full-width buffers inside its while_loop
    carry and pays full-width splices at every epoch boundary), the host
    loop hands this function buffers ALREADY at the current bucket sizes —
    the shapes are the static bucket choice, no ``lax.switch`` product and
    no full-width staging.  Runs rounds until the forest completes or —
    checked every ``compaction`` rounds — a strictly smaller edge or
    vertex bucket becomes reachable, then performs the contraction
    transform at prefix width: relabel surviving roots, flush CAS commit
    slots, refresh coverage under the post-hook parent, rewrite endpoints
    into the new dense space, and build the live-prefix permutation.  The
    host reads the returned scalars, picks the next bucket pair, and calls
    :func:`contract_slice_host` to materialize the smaller buffers.

    The transform is computed even when ``done`` flips (one wasted
    O(bucket) pass on the final epoch) so the host needs only a single
    device round-trip per epoch.
    """
    sz_v = parent.shape[0]
    sz_e = src.shape[0]
    e_sizes = scan_bucket_sizes(sz_e)
    v_sizes = vertex_bucket_sizes(sz_v)
    # Vertex-only shrinks pay off only when vertex-sized per-round work is
    # a real fraction of the round (measured: at E >> V the round cost is
    # identical across vertex buckets, so contracting for V alone is pure
    # transform overhead).  Static in the bucket pair, so it folds away.
    v_matters = 2 * sz_v >= sz_e
    state = BoruvkaState(parent, mst_mask, covered, num_rounds, num_waves,
                         jnp.zeros((), bool), committed)

    def cond(c):
        st, live_e, live_v, in_epoch = c
        e_shrink = scan_bucket_index(e_sizes, live_e) < len(e_sizes) - 1
        v_shrink = scan_bucket_index(v_sizes, live_v) < len(v_sizes) - 1
        # Dedup unlock: once V'^2 fits the pair table (<= sz_e), the
        # multi-edge dedup bounds the live set by V'^2/2 — a guaranteed
        # edge-bucket collapse on dense classes whose live count never
        # decays on its own.  float32: V'^2 overflows int32 at V' > 46341.
        dedup = (live_v.astype(jnp.float32) ** 2
                 <= jnp.float32(sz_e)) & (len(e_sizes) > 1)
        shrink = e_shrink | (v_shrink & v_matters) | dedup
        cadence = (st.num_rounds % compaction) == 0
        # `in_epoch` guards progress: the entry state may already satisfy
        # the dedup condition (it fired last epoch too), so require at
        # least one round before handing back to the host.
        return ~st.done & ~(cadence & shrink & (in_epoch > 0))

    def body(c):
        st, _, _, in_epoch = c
        st = boruvka_round(st, src, dst, rank, full_src, full_dst, order,
                           root_map, variant=variant, track_covered=True,
                           num_nodes=sz_v, max_lock_waves=max_lock_waves)
        live_e = jnp.sum(~st.covered).astype(jnp.int32)
        live_v = count_active_roots(st.parent, num_active)
        return st, live_e, live_v, in_epoch + 1

    st, _, _, _ = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(sz_e, jnp.int32), num_active,
                     jnp.zeros((), jnp.int32)))

    iota = jnp.arange(sz_v, dtype=jnp.int32)
    isroot = (st.parent == iota) & (iota < num_active)
    if use_kernel:
        from repro.kernels.relabel_vertices.ops import relabel_vertices
        new_id, n_new = relabel_vertices(isroot)
    else:
        new_id, n_new = relabel_roots(isroot)
    mst_mask = st.mst_mask
    if committed is not None:
        # Slots are addressed by contracted id, which the relabeling is
        # about to reuse: flush now (sentinel slots scatter out of bounds
        # -> dropped); contract_slice_host rebuilds fresh sentinel slots.
        mst_mask = mst_mask.at[st.committed].set(True, mode="drop")
    cu = st.parent[src]
    cv = st.parent[dst]
    cov = st.covered | (cu == cv)  # post-hook coverage refresh
    nsrc = new_id[cu]
    ndst = new_id[cv]
    cov = dedup_parallel_edges(cov, nsrc, ndst, rank, n_new)
    if use_kernel:
        from repro.kernels.compact_edges.ops import compact_edges
        perm, live = compact_edges(cov)
    else:
        perm, live = live_prefix_permutation(cov)
    return (st.done, st.num_rounds, st.num_waves, mst_mask,
            nsrc, ndst, perm, live,
            new_id[st.parent[root_map]], n_new)


@jax.named_scope("mst.compact")
def respread_ranks(lane_rank, order):
    """Renumber surviving edge ranks to a dense ``[0, live)`` prefix at an
    epoch boundary (the ROADMAP PR-7 follow-up).

    ``lane_rank``: (E',) packed live lanes' ranks in the PREVIOUS epoch's
    rank space, INT_SENTINEL on pad lanes.  ``order``: that space's decode
    table (``order[r]`` = original edge id holding rank r).  Returns
    ``(new_rank, new_order)``: the j-th smallest surviving rank becomes j,
    and ``new_order`` — now only E' entries — decodes the new space
    straight to original edge ids.

    The renumbering is monotone (stable argsort of unique ranks), so every
    rank comparison the hooking machinery makes is unchanged —
    bit-identical rounds/waves/mask, the same argument as the contraction
    relabel itself.  What it buys: ranks stay dense in the CURRENT edge
    bucket, so the multi-edge dedup's pair table and every decode gather
    shrink with the epoch instead of staying O(E_full) — without it,
    repeated contractions keep global ranks and the first dedup's
    surviving ranks are spread across the full original range.
    """
    e = lane_rank.shape[0]
    sidx = jnp.argsort(lane_rank, stable=True).astype(jnp.int32)
    new_rank = jnp.zeros((e,), jnp.int32).at[sidx].set(
        jnp.arange(e, dtype=jnp.int32))
    new_rank = jnp.where(lane_rank == INT_SENTINEL, INT_SENTINEL, new_rank)
    # Pad slots (lane_rank == sentinel) gather out of bounds -> fill 0;
    # they are never decoded (a candidate's rank is always < live).
    new_order = order.at[lane_rank[sidx]].get(mode="fill", fill_value=0)
    return new_rank, new_order


@functools.partial(jax.jit, static_argnames=("new_e", "new_v", "e_full"))
@jax.named_scope("mst.compact")
def contract_slice_host(nsrc, ndst, rank, order, perm, live, *, new_e: int,
                        new_v: int, e_full: int):
    """Materialize the next epoch's bucket-sized buffers from
    :func:`contract_epoch_host`'s full-prefix outputs: gather the live
    lanes (``perm`` packs them first; the host chose ``new_e`` >= live),
    re-spread the surviving ranks to a dense prefix (with the matching
    shrunken decode table), and reset the vertex-side state — identity
    parent, sentinel commit slots — at the contracted size."""
    prefix = perm[:new_e]
    pad = jnp.arange(new_e, dtype=jnp.int32) >= live
    lane_rank = jnp.where(pad, INT_SENTINEL, rank[prefix])
    new_rank, new_order = respread_ranks(lane_rank, order)
    return (nsrc[prefix], ndst[prefix], new_rank, new_order,
            jnp.arange(new_v, dtype=jnp.int32),       # parent: identity
            pad,                                      # covered
            jnp.full((new_v,), e_full, jnp.int32))    # CAS commit slots


@jax.named_scope("mst.finish")
def contracted_parent_original_ids(root_map, num_nodes: int) -> jnp.ndarray:
    """Translate the contracted component ids back to an original-id
    parent array: every vertex points at the minimum original vertex of
    its component (a valid fully-compressed union-find labeling, the
    canonical choice since contraction erases the hook-order roots)."""
    v_iota = jnp.arange(num_nodes, dtype=jnp.int32)
    rep = jax.ops.segment_min(v_iota, root_map, num_segments=num_nodes)
    return rep[root_map]


def make_scan_branches(sizes: Tuple[int, ...], num_nodes: int):
    """Bucketed candidate-scan branches for the mesh engines.

    Each branch takes ``(parent, covered, frontier)`` and returns the
    spliced-back covered array plus the shard-local ``(V,)`` candidate
    minima over its static prefix — everything shard-local, so devices in
    different buckets diverge safely; the cross-shard ``pmin`` stays with
    the caller (a collective inside a divergent branch would deadlock,
    which is also why the mesh engines cannot reuse ``boruvka_epoch``'s
    whole-round-in-branch structure).
    """
    def scan_branch(sz):
        @jax.named_scope("mst.scan")
        def scan(ops):
            parent, covered, f = ops
            cu_e = parent[f.src[:sz]]
            cv_e = parent[f.dst[:sz]]
            self_edge = cu_e == cv_e
            new_cov = covered[:sz] | self_edge
            key = jnp.where(new_cov, INT_SENTINEL, f.rank[:sz])
            local_best = candidate_min_edges(key, cu_e, cv_e, num_nodes)
            return covered.at[:sz].set(new_cov), local_best
        return scan

    return [scan_branch(sz) for sz in sizes]


@jax.named_scope("mst.compact")
def maybe_pack_frontier(state: BoruvkaState, frontier: Frontier,
                        sizes: Tuple[int, ...], compaction: int
                        ) -> Tuple[BoruvkaState, Frontier]:
    """Per-round gated pack for the mesh engines (shard-local, no
    collective): pack only on the cadence AND only when the fresh live
    count buys a smaller pow2 bucket.

    The identity branch of the cond stages this device's frontier buffers
    even on non-pack rounds — the overhead that pushed the single/batched
    engines to the epoch structure (DESIGN.md §2b) — but here the staged
    buffers are the O(E/S) shard, not the full edge list, and the epoch
    alternative is off the table because the per-round ``pmin`` cannot
    move inside a divergent switch branch.
    """
    live_now = jnp.sum(~state.covered).astype(jnp.int32)
    do = (~state.done & (state.num_rounds % compaction == 0)
          & (scan_bucket_index(sizes, live_now)
             < scan_bucket_index(sizes, frontier.live)))
    frontier, covered = jax.lax.cond(
        do,
        lambda args: compact_frontier_bucketed(*args, sizes=sizes),
        lambda args: args, (frontier, state.covered))
    return state._replace(covered=covered), frontier


def scan_bucket_sizes(e_scan: int,
                      min_bucket: int = MIN_SCAN_BUCKET) -> Tuple[int, ...]:
    """Static power-of-two prefix lengths ``[min_bucket, ..., e_scan]``.

    The ``lax.switch`` over these sizes is what bounds jit specialization to
    log2(E) branches under JAX's static shapes — the same pow2-bucket idea
    as ``graphs/batching.py``, applied to the scan prefix.
    """
    sizes = []
    b = min(min_bucket, e_scan)
    while b < e_scan:
        sizes.append(b)
        b <<= 1
    sizes.append(e_scan)
    return tuple(sizes)


def scan_bucket_index(sizes: Tuple[int, ...], live) -> jnp.ndarray:
    """Index of the smallest bucket that covers ``live`` lanes (traced)."""
    return jnp.searchsorted(jnp.asarray(sizes, jnp.int32),
                            live.astype(jnp.int32), side="left"
                            ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Per-round building blocks.
# ---------------------------------------------------------------------------

@jax.named_scope("mst.scan")
def candidate_min_edges(key, cu, cv, num_nodes):
    """Per-component minimum outgoing edge rank (paper lines 15-28).

    ``key`` already carries INT_SENTINEL for covered/self edges.  Each edge
    offers itself to the components of *both* endpoints (the graph is
    undirected), mirroring the paper's two minimum[] updates per edge.
    """
    best_u = jax.ops.segment_min(key, cu, num_segments=num_nodes)
    best_v = jax.ops.segment_min(key, cv, num_segments=num_nodes)
    return jnp.minimum(best_u, best_v)  # (V,) rank or INT_SENTINEL


@jax.named_scope("mst.hook")
def resolve_candidates(best, order, full_src, full_dst, parent,
                       root_map=None):
    """Decode per-component candidate rank -> (edge id, endpoints, partner).

    Requires the *replicated-topology* arrays ``order``/``full_src``/
    ``full_dst``; the shard-local engine replaces this step with its
    owner-decode collective (``sharded_mst``) and calls
    ``partner_components`` on the decoded endpoints instead.

    Under contraction (``root_map`` not None) the topology arrays still
    hold ORIGINAL vertex ids, so the decoded endpoints are translated
    into the contracted space before the parent lookups; the returned
    ``end_u``/``end_v`` are contracted ids, which is what the lock
    variant's per-wave re-find needs.
    """
    has = best < INT_SENTINEL
    # Single guarded gather: a sentinel rank is out of bounds for `order`,
    # so fill-mode returns the same 0 the old clip-then-where produced —
    # one gather instead of clip + gather + select.
    cand_edge = order.at[best].get(mode="fill", fill_value=0)
    end_u = full_src[cand_edge]
    end_v = full_dst[cand_edge]
    if root_map is not None:
        end_u = root_map[end_u]
        end_v = root_map[end_v]
    other, iota = partner_components(parent, has, end_u, end_v)
    return has, cand_edge, end_u, end_v, other, iota


@jax.named_scope("mst.hook")
def partner_components(parent, has, end_u, end_v):
    """Partner root of each component's candidate edge.

    One endpoint root is the component itself; ``other`` is the far side.
    """
    num_nodes = parent.shape[0]
    iota = jnp.arange(num_nodes, dtype=jnp.int32)
    cu = parent[end_u]
    cv = parent[end_v]
    other = jnp.where(has, cu + cv - iota, iota)
    return other, iota


def commit_edges(mst_mask, cand_edge, commit):
    """Scatter-commit candidate edges; non-committers scatter out of bounds
    (dropped), mirroring 'Add edge minimum[v] to the set M' under guard.
    Unscoped: it reads as its caller's phase (``mst.hook``, ``mst.lock``)."""
    e = mst_mask.shape[0]
    idx = jnp.where(commit, cand_edge, e)  # e == out-of-bounds -> dropped
    return mst_mask.at[idx].set(True, mode="drop")


# ---------------------------------------------------------------------------
# Hooking variants - the paper's two synchronization schemes, data-parallel.
# ---------------------------------------------------------------------------

@jax.named_scope("mst.hook")
def hook_cas(parent, has, cand_edge, other, iota):
    """CAS-variant hooking (paper §2.2.2).

    Every component atomically swings its parent pointer along its minimum
    edge.  Racing CASes on *distinct* parents all succeed => chains are
    allowed.  The only possible cycle is a mutual 2-cycle (both components
    picked the same edge - provably the same edge under distinct weights);
    it is broken deterministically by keeping the smaller root.
    """
    # Hooking roots swing their pointer to `other`; everyone else keeps their
    # (already compressed) parent.  `has` is only ever True for roots.
    prop = jnp.where(has, other, parent)
    mutual = has & (prop != iota) & (prop[prop] == iota)
    keep_root = mutual & (iota < prop)  # smaller root survives the 2-cycle
    new_parent = jnp.where(keep_root, iota, prop)
    # A component whose pointer actually moved commits its candidate edge.
    # (The 2-cycle winner's edge equals the loser's edge; committed once,
    # scatter is idempotent anyway.)
    commit = has & (new_parent != iota)
    return new_parent, commit


@jax.named_scope("mst.lock")
def hook_lock_waves(parent, mst_mask, has, cand_edge, end_u, end_v,
                    *, max_waves: int, commit_fn=commit_edges):
    """Lock-variant hooking (paper §2.2.1), as propose-verify *retry waves*.

    One wave = one synchronous generation of the paper's lock protocol:

      Phase A (acquire): each hooking component r writes its id into the lock
      cell of *both* components; contention resolves deterministically by min
      (stand-in for the racy first-writer of the paper).
      Phase B (verify): r proceeds iff it holds both locks - the paper's
      re-read of lock_tid[C1]/lock_tid[C2] == tid - then *re-finds* both
      endpoints (lines 52-55) and commits only if they are still distinct.

    ``end_u``/``end_v`` are the (V,) vertex endpoints of each component's
    candidate edge (round-constant); the re-find reads ``parent`` at those
    endpoints each wave, so no replicated topology array is required —
    shard-local engines pass the endpoints from their owner-decode step.
    ``commit_fn(mask, cand_edge, granted)`` pluggably scatters committed
    edges (full-size mask for replicated engines, local shard otherwise).

    Holding both locks makes each wave's merge set a *matching*.  The paper's
    threads simply retry failed acquisitions while scanning their remaining
    vertices within the round; the synchronous analogue is to re-run waves
    with the round's fixed minimum[] candidates until no active candidate
    remains (or ``max_waves`` is hit - leftovers retry in the next round,
    which recomputes minima; correctness is unaffected).

    SPMD finding (see EXPERIMENTS.md): once a giant component forms, every
    surviving component's min edge points into it, and lock arbitration on
    the giant's cell admits ONE union per wave - lock-style serialization
    that the paper's asynchronous multicore hides at ~100ns/union but
    lockstep SPMD pays at a full O(V) wave each.  This is the structural
    reason the CAS variant wins, and why its win is far larger on TPU than
    the paper's 1.15x on multicore.

    Progress: the smallest active root always wins both its locks, so every
    wave commits >= 1 union while any candidate is valid.
    """
    num_nodes = parent.shape[0]
    iota = jnp.arange(num_nodes, dtype=jnp.int32)

    def wave(carry):
        parent, mst, active, waves = carry
        cu = parent[end_u]
        cv = parent[end_v]
        isroot = parent == iota
        # owner/root check + re-find staleness (paper lines 38-43).
        valid = active & isroot & (cu != cv) & ((cu == iota) | (cv == iota))
        other = jnp.where(valid, cu + cv - iota, iota)
        # Phase A: acquire both lock cells (scatter-min arbitration).
        writer = jnp.where(valid, iota, INT_SENTINEL)
        lock = jnp.full((num_nodes,), INT_SENTINEL, jnp.int32)
        lock = lock.at[jnp.where(valid, iota, num_nodes)].min(
            writer, mode="drop")
        lock = lock.at[jnp.where(valid, other, num_nodes)].min(
            writer, mode="drop")
        # Phase B: verify both locks held, then commit.
        granted = valid & (lock[iota] == iota) & (lock[other] == iota)
        parent = parent.at[jnp.where(granted, other, num_nodes)].set(
            iota, mode="drop")
        mst = commit_fn(mst, cand_edge, granted)
        parent = pointer_jump(parent)
        active = valid & ~granted
        return parent, mst, active, waves + 1

    def cond(carry):
        _, _, active, waves = carry
        return jnp.any(active) & (waves < max_waves)

    parent, mst_mask, _, waves = jax.lax.while_loop(
        cond, wave, (parent, mst_mask, has, jnp.zeros((), jnp.int32)))
    return parent, mst_mask, waves


# ---------------------------------------------------------------------------
# One Borůvka round (replicated-topology layout).
# ---------------------------------------------------------------------------

@jax.named_scope("mst.hook")
def hook_commit_round(state: BoruvkaState, best, order, full_src, full_dst,
                      root_map=None, *, variant: str,
                      max_lock_waves: int = 16) -> BoruvkaState:
    """The back half of one Borůvka round, shared by every candidate-search
    layout: decode the per-component candidate ranks (``best``), hook
    (cas/lock), commit, and advance the round/wave/done accounting.

    ``best`` is the (V,) per-component minimum outgoing edge rank
    (INT_SENTINEL = no candidate) — however it was computed: the edge-list
    engines' ``candidate_min_edges`` scan, or the spmm engine's row-blocked
    semiring reduction (``core/spmm_mst.py``).  Identical ``best`` in =>
    bit-identical hooking decisions out, which is exactly the conformance
    contract across engines.  ``state.covered`` passes through untouched:
    coverage is the candidate-search half's bookkeeping (the spmm engine
    keeps none).
    """
    has, cand_edge, end_u, end_v, other, iota = resolve_candidates(
        best, order, full_src, full_dst, state.parent, root_map)
    committed = state.committed
    if variant == "cas":
        new_parent, commit = hook_cas(state.parent, has, cand_edge, other,
                                      iota)
        if committed is None:
            mst_mask = commit_edges(state.mst_mask, cand_edge, commit)
        else:
            # Write-once commit slots: (V,) elementwise, no scatter.
            mst_mask = state.mst_mask
            committed = jnp.where(commit, cand_edge, committed)
        new_parent = pointer_jump(new_parent)
        waves = jnp.ones((), jnp.int32)
    elif variant == "lock":
        new_parent, mst_mask, waves = hook_lock_waves(
            state.parent, state.mst_mask, has, cand_edge, end_u, end_v,
            max_waves=max_lock_waves)
    else:
        raise ValueError(f"unknown variant {variant!r}")
    # Done when no component saw an outgoing edge (forest complete).
    done = ~jnp.any(has)
    return BoruvkaState(new_parent, mst_mask, state.covered,
                        state.num_rounds + jnp.where(done, 0, 1),
                        state.num_waves + jnp.where(done, 0, waves), done,
                        committed)


def boruvka_round(state: BoruvkaState, scan_src, scan_dst, scan_rank,
                  full_src, full_dst, order, root_map=None, *, variant: str,
                  track_covered: bool, num_nodes: int,
                  max_lock_waves: int = 16) -> BoruvkaState:
    """One round: min-edge search over scan lanes, hooking, compression.

    ``root_map`` (contract-Borůvka only) translates original-id endpoints
    decoded from the replicated topology into the contracted vertex space;
    the scan lanes themselves are already contracted-id.
    """
    with jax.named_scope("mst.scan"):
        cu_e = state.parent[scan_src]
        cv_e = state.parent[scan_dst]
        self_edge = cu_e == cv_e
        new_covered = state.covered | self_edge  # "graph_edge[E].covered = 1"
        key = jnp.where(new_covered, INT_SENTINEL, scan_rank)
        best = candidate_min_edges(key, cu_e, cv_e, num_nodes)
    out = hook_commit_round(state, best, order, full_src, full_dst,
                            root_map, variant=variant,
                            max_lock_waves=max_lock_waves)
    return out._replace(
        covered=new_covered if track_covered else state.covered)


@jax.named_scope("mst.compact")
def boruvka_epoch(state: BoruvkaState, frontier: Frontier,
                  full_src, full_dst, order, *, round_fn,
                  sizes: Tuple[int, ...], compaction: int,
                  use_kernel: bool = False
                  ) -> Tuple[BoruvkaState, Frontier]:
    """One *bucket epoch*: rounds at a fixed pow2 prefix, then one pack.

    ``round_fn(state, scan_src, scan_dst, scan_rank, full_src, full_dst,
    order)`` is the round body — ``boruvka_round`` with its static kwargs
    bound for the single engine, its ``jax.vmap`` for the batched engine.

    The ``lax.switch`` over the static ``sizes`` picks the bucket covering
    the current live count; the chosen branch runs an inner ``while_loop``
    of rounds over that statically-sliced prefix until either the forest
    completes or — checked every ``compaction`` rounds — the live count
    has dropped to a smaller bucket.  The exit check reads the round's own
    covered update (a coverage snapshot fresh as of round start, so it
    costs nothing); the only extra coverage work is ONE refresh under the
    post-hook parent at pack time, so the pack sees the self edges the
    closing epoch's merges created.  The pack runs exactly once per epoch,
    bounded to the old bucket.  Hoisting the bucket switch, the refresh,
    and the pack out of the round loop keeps the per-round cost at a pure
    O(bucket) scan: a per-round conditional pack stages identity-branch
    buffers every round, and fully unrolling the epochs (no switch) makes
    every level pay its pack — both measured dead ends, recorded in
    EXPERIMENTS.md §Compaction.

    Slicing is on the *last* axis, so the same helper serves the batched
    engine's (B, E_pad) layout; every cross-lane decision (bucket index,
    cadence, exit) reduces with ``jnp.max`` OUTSIDE any vmap — a vmapped
    switch would execute every branch and erase the saving.
    """
    idx = scan_bucket_index(sizes, jnp.max(frontier.live))

    def branch(i, sz):
        def run(ops):
            st, f = ops
            src = f.src[..., :sz]
            dst = f.dst[..., :sz]
            rank = f.rank[..., :sz]

            def inner_cond(c):
                st_i, live = c
                shrinkable = scan_bucket_index(sizes, jnp.max(live)) < i
                cadence = (jnp.max(st_i.num_rounds) % compaction) == 0
                return ~jnp.all(st_i.done) & ~(cadence & shrinkable)

            def inner_body(c):
                st_i, _ = c
                st_i = round_fn(st_i, src, dst, rank,
                                full_src, full_dst, order)
                live = jnp.sum(~st_i.covered, axis=-1).astype(jnp.int32)
                return st_i, live

            sub0 = st._replace(covered=st.covered[..., :sz])
            sub, _ = jax.lax.while_loop(inner_cond, inner_body,
                                        (sub0, f.live))
            # Pack-time coverage refresh: one pair of prefix-width gathers
            # under the post-hook parent (the in-round covered bit lags
            # hooking by one round).
            cov_sz = sub.covered | (
                jnp.take_along_axis(sub.parent, src, axis=-1)
                == jnp.take_along_axis(sub.parent, dst, axis=-1))
            covered = st.covered.at[..., :sz].set(cov_sz)
            f2, covered = _pack_prefix(f, covered, sz, use_kernel)
            return sub._replace(covered=covered), f2
        return run

    return jax.lax.switch(idx, [branch(i, sz) for i, sz in enumerate(sizes)],
                          (state, frontier))
