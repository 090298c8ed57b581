"""Sparse-semiring (GraphBLAS-style) MSF engine — DESIGN.md §2d.

Algebraic reformulation of the Borůvka candidate search: one round's
per-component minimum outgoing edge is a sparse matrix-vector product
over the (min, select) semiring

    best[c] = MIN over slots (u, v, key) with label[u] = c
              of  ( key   if label[v] != label[u]
                    SENT  otherwise )

i.e. the "multiply" is the cut filter (keep a slot iff it crosses the
current component labeling) and the "add" is min — the (min, +)-style
candidate semiring with a (rank-encoded weight, edge id) payload packed
into one dense int32 rank.  GraphBLAS MSF formulations (GBTL, LAGraph)
express Borůvka exactly this way; the paper's per-thread ``minimum[]``
scan is the same reduction in edge-list order.

What the reformulation buys on this stack: the edge-list engines reduce
with an (E,)-wide ``segment_min`` scatter whose cost is pinned to the
*scan* size, while here the reduction runs row-blocked over a device-side
ELL(+overflow) adjacency (``graphs/csr_device.py``): a fixed-shape
``(V, D)`` gather/filter/row-min plus a V-sized segment combine —
vertex-dimension cost, contiguous accesses, no big scatter.  Measured on
Graph100K_6 mid-solve the ELL selection is ~4x faster than the edge-list
scan.  ``kernels/gnn_spmm.gather_segment_min`` is the Pallas kernel
of the same semiring reduction; the jnp formulation here is the default
path on every backend (the TPU compiler refuses the kernel, see
:func:`resolve_kernel`), and both are pinned equal in the kernel sweep.

Everything *after* candidate selection — decode, cas/lock hooking,
commit, round accounting — is the shared ``engine.hook_commit_round``,
so identical ``best`` vectors make this engine's rounds, waves and mask
bit-identical to the other six engines (the conformance contract).

Layout maintenance replaces frontier compaction: ``compaction=k`` means
every k rounds the engine *rebuilds* the ELL layout from the surviving
cut edges (host epoch loop, same pow2-bucket idiom as
``mst._contracted_host_loop``), with the rank re-spread keeping keys
dense; ``contraction=True`` additionally relabels supervertices so the
row dimension — which is this engine's per-round cost — shrinks too.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import annotate, phase as _obs_phase
from repro.core.types import Graph, MSTResult, INT_SENTINEL, ensure_sized
from repro.core.engine import (
    BoruvkaState,
    contract_slice_host,
    contracted_parent_original_ids,
    count_active_roots,
    dedup_parallel_edges,
    finish_result,
    hook_commit_round,
    init_state,
    live_prefix_permutation,
    materialize_commits,
    rank_edges_host,
    relabel_roots,
    respread_ranks,
    scan_bucket_index,
    scan_bucket_sizes,
    validate_variant,
    vertex_bucket_sizes,
)
from repro.core.mst import _bucket_cover
from repro.graphs.csr_device import EllGraph, ell_from_edges, \
    ell_from_edges_host
from repro.kernels.gnn_spmm.ops import gather_segment_min


@jax.named_scope("mst.scan")
def spmm_candidates(ell: EllGraph, parent) -> jnp.ndarray:
    """One candidate-semiring SpMV: (V,) per-component min outgoing rank.

    ELL block: gather each slot's neighbor component, filter slots that
    do not cross the cut (including empty slots, whose key is already
    SENT), row-min to the per-VERTEX best, then one V-sized segment_min
    folds vertices into their components.  Overflow tail: the same
    filter + segment_min in COO form.  Every undirected edge owns two
    slots (one per endpoint row), so each component sees its full
    incident cut — the same per-component key multisets as
    ``engine.candidate_min_edges``, hence bit-identical minima.
    """
    v = parent.shape[0]
    assert ell.num_rows == v, (ell.num_rows, v)
    # Empty slots aim at row V: the fill component V can never equal a
    # real parent, but their SENT key never wins a min anyway.
    pc = parent.at[ell.ell_col].get(mode="fill", fill_value=v)
    key = jnp.where(pc != parent[:, None], ell.ell_key, INT_SENTINEL)
    best = jax.ops.segment_min(jnp.min(key, axis=1), parent,
                               num_segments=v)
    if ell.ovf_row.shape[0]:
        # Pad slots are (V, V, SENT): clip keeps the gathers in bounds
        # and the self-pair filter plus SENT key keep them inert.
        pr = parent.at[ell.ovf_row].get(mode="clip")
        po = parent.at[ell.ovf_col].get(mode="clip")
        okey = jnp.where(pr != po, ell.ovf_key, INT_SENTINEL)
        best = jnp.minimum(
            best, jax.ops.segment_min(okey, pr, num_segments=v))
    return best


@jax.named_scope("mst.scan")
def spmm_candidates_kernel(ell: EllGraph, parent) -> jnp.ndarray:
    """``spmm_candidates`` through the Pallas ``gather_segment_min``
    kernel — the same (min, cut-filter) semiring; opt-in only
    (:func:`resolve_kernel`).

    The ELL block plus overflow tail flatten to one slot stream
    (row, col, key); the kernel's cut filter and scatter-min see exactly
    the per-component key multisets the jnp path reduces:

      * empty ELL slots carry ``col == V`` and a SENT key — the V+1-row
        label append inside ``gather_segment_min`` keeps the gather in
        bounds, and SENT never wins a min (the jnp path's fill-gather
        reaches the same inertness via ``fill_value=v``);
      * overflow pad slots are (V, V, SENT): self-labeled at the
        sentinel row, so the cut filter drops them (jnp: clip + self-pair
        filter).

    Identical contribution multisets + min associativity = bit-identical
    ``best`` vectors, which the kernel-path conformance cell pins.
    """
    v = ell.num_rows
    d = ell.ell_col.shape[1]
    row = jnp.broadcast_to(
        jnp.arange(v, dtype=jnp.int32)[:, None], (v, d)).reshape(-1)
    col = ell.ell_col.reshape(-1)
    key = ell.ell_key.reshape(-1)
    if ell.ovf_row.shape[0]:
        row = jnp.concatenate([row, ell.ovf_row])
        col = jnp.concatenate([col, ell.ovf_col])
        key = jnp.concatenate([key, ell.ovf_key])
    # Slots are component-labeled through ``parent`` itself, so the
    # kernel's out[label] accumulator IS the per-component best vector.
    return gather_segment_min(row, col, key, parent, num_nodes=v)


def resolve_kernel(kernel: Optional[bool] = None) -> bool:
    """Candidate-path gate: the jnp reduction is the default on every
    backend, the Pallas ``gather_segment_min`` kernel runs only when asked
    for with ``kernel=True``.

    The kernel reads labels at data-dependent 1-element offsets of VMEM,
    which the TPU compiler refuses (it must prove every vector load
    aligned to the tiling), and keeps the whole (V+1,) label vector and
    accumulator resident in VMEM.  Off-TPU it runs in interpret mode,
    where the conformance cell pins it equal to the jnp path.
    """
    return bool(kernel)


@functools.partial(jax.jit,
                   static_argnames=("variant", "max_lock_waves", "kernel"))
def _spmm_msf_jit(graph: Graph, ell: EllGraph, order, *, variant: str,
                  max_lock_waves: int, kernel: bool = False) -> MSTResult:
    """compaction=0 driver: one jitted while_loop over a static layout.

    The covered bit is the edge-list engines' scan bookkeeping; the
    semiring filter re-derives coverage from the labeling each round, so
    the state carries a (1,) dummy."""
    num_nodes = graph.num_nodes
    init = init_state(num_nodes, graph.num_edges, 1,
                      commit_slots=variant == "cas")

    def cond(s):
        return ~s.done

    select = spmm_candidates_kernel if kernel else spmm_candidates

    def body(s):
        best = select(ell, s.parent)
        return hook_commit_round(s, best, order, graph.src, graph.dst,
                                 variant=variant,
                                 max_lock_waves=max_lock_waves)

    final = materialize_commits(jax.lax.while_loop(cond, body, init))
    return finish_result(graph, final, final.num_rounds)


@functools.partial(
    jax.jit, static_argnames=("variant", "max_lock_waves", "compaction",
                              "contraction", "kernel"))
def _spmm_epoch(parent, committed, mst_mask, num_rounds, num_waves,
                ell: EllGraph, esrc, edst, ekey, order_tbl, full_src,
                full_dst, root_map, num_active, *, variant: str,
                max_lock_waves: int, compaction: int, contraction: bool,
                kernel: bool = False):
    """One spmm epoch at fixed layout shapes (host epoch loop body).

    Rounds reduce over the CURRENT ELL layout until the forest completes
    or — checked every ``compaction`` rounds — a smaller edge bucket (or,
    under contraction, vertex bucket / the dedup unlock) is reachable;
    then one epoch-boundary transform over the edge spine
    (``esrc``/``edst``/``ekey``, the packed lane view the ELL was built
    from) computes everything the host needs to rebuild a smaller layout.

    Unlike ``contract_epoch_host`` the rounds never touch the spine — the
    whole point of the engine is that per-round work is O(V*D + O), so
    the live-edge/supervertex counts are refreshed via ``lax.cond`` only
    on the cadence instead of every round.
    """
    sz_v = parent.shape[0]
    sz_e = esrc.shape[0]
    e_sizes = scan_bucket_sizes(sz_e)
    v_sizes = vertex_bucket_sizes(sz_v)
    state = BoruvkaState(parent, mst_mask, jnp.zeros((1,), bool),
                         num_rounds, num_waves, jnp.zeros((), bool),
                         committed)
    rmap = root_map if contraction else None

    def cond(c):
        st, live_e, live_v, in_epoch = c
        shrink = scan_bucket_index(e_sizes, live_e) < len(e_sizes) - 1
        if contraction:
            # Row count IS this engine's per-round cost, so a vertex
            # shrink always pays (no 2V >= E gate as in the edge-list
            # epoch).  Dedup unlock as in contract_epoch_host.
            v_shrink = (scan_bucket_index(v_sizes, live_v)
                        < len(v_sizes) - 1)
            dedup = (live_v.astype(jnp.float32) ** 2
                     <= jnp.float32(sz_e)) & (len(e_sizes) > 1)
            shrink = shrink | v_shrink | dedup
        cadence = (st.num_rounds % compaction) == 0
        return ~st.done & ~(cadence & shrink & (in_epoch > 0))

    select = spmm_candidates_kernel if kernel else spmm_candidates

    def body(c):
        st, live_e, live_v, in_epoch = c
        best = select(ell, st.parent)
        st = hook_commit_round(st, best, order_tbl, full_src, full_dst,
                               rmap, variant=variant,
                               max_lock_waves=max_lock_waves)

        def refresh(_):
            le = jnp.sum((st.parent[esrc] != st.parent[edst])
                         & (ekey != INT_SENTINEL)).astype(jnp.int32)
            lv = (count_active_roots(st.parent, num_active)
                  if contraction else live_v)
            return le, lv

        live_e, live_v = jax.lax.cond(
            (st.num_rounds % compaction) == 0, refresh,
            lambda _: (live_e, live_v), None)
        return st, live_e, live_v, in_epoch + 1

    st, _, _, _ = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(sz_e, jnp.int32), num_active,
                     jnp.zeros((), jnp.int32)))

    # Epoch-boundary transform over the spine (computed even when done
    # flips — one wasted O(sz_e) pass buys a single round-trip per epoch).
    cu = st.parent[esrc]
    cv = st.parent[edst]
    cov = (cu == cv) | (ekey == INT_SENTINEL)
    mst_mask = st.mst_mask
    out_parent, out_committed = st.parent, st.committed
    if contraction:
        iota = jnp.arange(sz_v, dtype=jnp.int32)
        isroot = (st.parent == iota) & (iota < num_active)
        new_id, n_new = relabel_roots(isroot)
        if committed is not None:
            # Slots are addressed by contracted id, which the relabeling
            # is about to reuse: flush now; contract_slice_host rebuilds
            # fresh sentinel slots.
            mst_mask = mst_mask.at[st.committed].set(True, mode="drop")
        nsrc = new_id[cu]
        ndst = new_id[cv]
        cov = dedup_parallel_edges(cov, nsrc, ndst, ekey, n_new)
        root_map = new_id[st.parent[root_map]]
        num_active = n_new
    else:
        # Components persist across epochs: rewrite endpoints to their
        # current roots (still original-id space) so rebuilt layouts keep
        # shrinking D, and keep parent/commit slots live in the carry.
        nsrc, ndst = cu, cv
    perm, live = live_prefix_permutation(cov)
    return (st.done, st.num_rounds, st.num_waves, mst_mask, out_parent,
            out_committed, nsrc, ndst, perm, live, root_map, num_active)


@functools.partial(jax.jit, static_argnames=("new_e",))
def _spmm_slice(nsrc, ndst, rank, order, perm, live, *, new_e: int):
    """Non-contraction epoch boundary: pack the live spine lanes into the
    next pow2 edge bucket and re-spread their ranks (vertex-side state
    persists, unlike ``contract_slice_host``)."""
    prefix = perm[:new_e]
    pad = jnp.arange(new_e, dtype=jnp.int32) >= live
    lane_rank = jnp.where(pad, INT_SENTINEL, rank[prefix])
    new_rank, new_order = respread_ranks(lane_rank, order)
    return nsrc[prefix], ndst[prefix], new_rank, new_order


def _spmm_host_loop(graph: Graph, rank, order, *, variant: str,
                    max_lock_waves: int, compaction: int,
                    contraction: bool, kernel: bool = False) -> MSTResult:
    """Host epoch loop: rebuild the ELL layout between epochs.

    The spmm analogue of ``mst._contracted_host_loop``: buffer shapes ARE
    the current pow2 bucket choice, the host reads back the post-epoch
    scalars, slices the spine down, and refreshes the device layout
    (``ell_from_edges``) at the new size.  One jit specialization per
    visited (layout, spine) shape tuple, ~log E of them.
    """
    num_nodes = graph.num_nodes
    e_full = graph.num_edges
    e_sizes = scan_bucket_sizes(e_full)
    v_sizes = vertex_bucket_sizes(num_nodes)
    cas = variant == "cas"

    src, dst, rk = graph.src, graph.dst, rank
    order_tbl = order
    with annotate("ell_build"), _obs_phase("ell_build"):
        ell = ell_from_edges_host(src, dst, rk, num_nodes)
    parent = jnp.arange(num_nodes, dtype=jnp.int32)
    committed = jnp.full((num_nodes,), e_full, jnp.int32) if cas else None
    mst_mask = jnp.zeros((e_full,), bool)
    num_rounds = jnp.zeros((), jnp.int32)
    num_waves = jnp.zeros((), jnp.int32)
    root_map = (jnp.arange(num_nodes, dtype=jnp.int32) if contraction
                else None)
    num_active = jnp.asarray(num_nodes, jnp.int32)

    epochs = 0
    while True:
        with annotate("spmm_epoch"):
            (done, num_rounds, num_waves, mst_mask, parent, committed,
             nsrc, ndst, perm, live, root_map, num_active) = _spmm_epoch(
                parent, committed, mst_mask, num_rounds, num_waves, ell,
                src, dst, rk, order_tbl, graph.src, graph.dst, root_map,
                num_active, variant=variant,
                max_lock_waves=max_lock_waves, compaction=compaction,
                contraction=contraction, kernel=kernel)
        if bool(done):
            break
        epochs += 1
        if epochs > num_nodes:  # safety: can't exceed V epochs
            raise RuntimeError("spmm Borůvka failed to converge")
        new_e = _bucket_cover(e_sizes, int(live))
        if contraction:
            new_v = _bucket_cover(v_sizes, int(num_active))
            src, dst, rk, order_tbl, parent, _, slots = \
                contract_slice_host(nsrc, ndst, rk, order_tbl, perm, live,
                                    new_e=new_e, new_v=new_v,
                                    e_full=e_full)
            committed = slots if cas else None
            rows = new_v
        else:
            src, dst, rk, order_tbl = _spmm_slice(
                nsrc, ndst, rk, order_tbl, perm, live, new_e=new_e)
            rows = num_nodes
        with annotate("ell_refresh"), _obs_phase("ell_build"):
            ell = ell_from_edges(src, dst, rk, rows)

    if contraction:
        total = jnp.sum(jnp.where(mst_mask, graph.weight, 0.0))
        return MSTResult(
            parent=contracted_parent_original_ids(root_map, num_nodes),
            mst_mask=mst_mask,
            num_rounds=num_rounds,
            num_waves=num_waves,
            total_weight=total,
            num_components=num_active)
    final = BoruvkaState(parent, mst_mask, jnp.zeros((1,), bool),
                         num_rounds, num_waves, jnp.ones((), bool),
                         committed)
    final = materialize_commits(final)
    return finish_result(graph, final, num_rounds)


def spmm_msf(graph: Graph, *, num_nodes: Optional[int] = None,
             variant: str = "cas", max_lock_waves: int = 16,
             compaction: int = 0, contraction: bool = False,
             kernel: Optional[bool] = None) -> MSTResult:
    """Borůvka MSF via per-round semiring SpMV candidate selection.

    Args:
      graph: edge-list graph (static shapes), preferably sized.
      num_nodes: V (static); only needed for legacy unsized graphs.
      variant: "cas" or "lock" — the hooking machinery is shared with the
        edge-list engines, and conformance pins the decisions identical.
      compaction: 0 = one static ELL layout for the whole solve; k > 0 =
        host epoch loop that rebuilds the layout from the surviving cut
        edges every k rounds (the engine's layout-refresh analogue of
        frontier compaction — rebuilds shrink D and the overflow tail).
      contraction: additionally relabel supervertices at epoch boundaries
        so the ELL ROW count — the per-round cost — shrinks too.
        Requires ``compaction > 0``.
      kernel: route candidate selection through the Pallas
        ``gather_segment_min`` kernel instead of the jnp reduction.
        None/False (default) take the jnp path on every backend (see
        :func:`resolve_kernel`); True runs the kernel (interpret mode
        off-TPU — the conformance cell's path); both are bit-identical.
    """
    graph = ensure_sized(graph, num_nodes)
    validate_variant(variant)
    if contraction and not compaction:
        raise ValueError("contraction requires compaction > 0 "
                         "(layout rebuilds happen at epoch boundaries)")
    use_kernel = resolve_kernel(kernel)
    rank, order = rank_edges_host(graph.weight)
    if compaction:
        return _spmm_host_loop(graph, rank, order, variant=variant,
                               max_lock_waves=max_lock_waves,
                               compaction=compaction,
                               contraction=contraction,
                               kernel=use_kernel)
    with annotate("ell_build"), _obs_phase("ell_build"):
        ell = ell_from_edges_host(graph.src, graph.dst, rank,
                                  graph.num_nodes)
    return _spmm_msf_jit(graph, ell, order, variant=variant,
                         max_lock_waves=max_lock_waves,
                         kernel=use_kernel)
