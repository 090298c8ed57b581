"""Pallas TPU kernel for the Borůvka inner loop ("for all edges E").

TPU adaptation of the paper's per-thread edge scan (DESIGN.md §6):
  * edges are STREAMED from HBM in blocks (BlockSpec over the grid axis) -
    the paper's cache-unfriendly random edge walk becomes sequential DMA;
  * the per-vertex minimum array ("minimum[]") is VMEM-RESIDENT for the
    whole sweep (index_map pins block 0 every step), so the scatter-min
    read-modify-write never round-trips HBM - on a multicore CPU this is
    exactly the line-bouncing the paper's owner_tid[] partitioning tries to
    avoid;
  * TPU grid steps execute sequentially on a core => the accumulation is
    race-free by construction: the scatter-min *is* the atomic CAS loop of
    the paper, with the hardware serialization for free.

The irregular per-edge update runs on the scalar unit via fori_loop; the
payload is a single int32, so the sweep is DMA-bound on the edge stream -
the right regime for this kernel (see EXPERIMENTS.md §Perf).

The batched variant extends the grid to ``(batch, edge_block)``: every
batch lane streams its own edge row while its ``minimum[]`` row stays
VMEM-resident across that lane's edge steps (index_map pins the output row
per lane, re-initialized when the edge axis restarts).  Grid iteration is
row-major, so lane b's edge sweep is contiguous - the same sequential-DMA
shape as the single-graph kernel, repeated per lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import INT_SENTINEL


def _kernel(keys_ref, cu_ref, cv_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, INT_SENTINEL)

    block = keys_ref.shape[0]

    def body(i, _):
        k = keys_ref[i]
        u = cu_ref[i]
        v = cv_ref[i]
        # scatter-min into the VMEM-resident minimum[] (both endpoints:
        # undirected edge offers itself to both components).
        out_ref[pl.ds(u, 1)] = jnp.minimum(out_ref[pl.ds(u, 1)], k)
        out_ref[pl.ds(v, 1)] = jnp.minimum(out_ref[pl.ds(v, 1)], k)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def segment_min_edges_pallas(keys, cu, cv, num_nodes: int,
                             block_edges: int = 4096,
                             interpret: bool = True):
    """keys/cu/cv: (E,) int32 -> (V,) int32 per-vertex min key.

    E must be a multiple of block_edges (pad with INT_SENTINEL keys).
    VMEM budget: block_edges*3*4B streamed + num_nodes*4B resident.
    """
    e = keys.shape[0]
    assert e % block_edges == 0, (e, block_edges)
    grid = (e // block_edges,)
    spec_e = pl.BlockSpec((block_edges,), lambda i: (i,))
    spec_out = pl.BlockSpec((num_nodes,), lambda i: (0,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec_e, spec_e, spec_e],
        out_specs=spec_out,
        out_shape=jax.ShapeDtypeStruct((num_nodes,), jnp.int32),
        interpret=interpret,
    )(keys, cu, cv)


def _batched_kernel(keys_ref, cu_ref, cv_ref, out_ref):
    # Edge axis restarts at 0 for each batch lane => re-init this lane's
    # VMEM-resident minimum[] row.
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, INT_SENTINEL)

    block = keys_ref.shape[1]

    lane = pl.ds(0, 1)  # block shape is (1, ...): single-lane row

    def body(i, _):
        k = keys_ref[lane, pl.ds(i, 1)]
        u = cu_ref[lane, pl.ds(i, 1)][0, 0]
        v = cv_ref[lane, pl.ds(i, 1)][0, 0]
        out_ref[lane, pl.ds(u, 1)] = jnp.minimum(out_ref[lane, pl.ds(u, 1)],
                                                 k)
        out_ref[lane, pl.ds(v, 1)] = jnp.minimum(out_ref[lane, pl.ds(v, 1)],
                                                 k)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def batched_segment_min_edges_pallas(keys, cu, cv, num_nodes: int,
                                     block_edges: int = 4096,
                                     interpret: bool = True):
    """keys/cu/cv: (B, E) int32 -> (B, V) int32 per-lane per-vertex min key.

    E must be a multiple of block_edges (pad with INT_SENTINEL keys).
    VMEM budget per grid step: block_edges*3*4B streamed + num_nodes*4B
    resident (one lane's minimum[] row).
    """
    b, e = keys.shape
    assert e % block_edges == 0, (e, block_edges)
    grid = (b, e // block_edges)
    spec_e = pl.BlockSpec((1, block_edges), lambda bi, i: (bi, i))
    spec_out = pl.BlockSpec((1, num_nodes), lambda bi, i: (bi, 0))
    return pl.pallas_call(
        _batched_kernel,
        grid=grid,
        in_specs=[spec_e, spec_e, spec_e],
        out_specs=spec_out,
        out_shape=jax.ShapeDtypeStruct((b, num_nodes), jnp.int32),
        interpret=interpret,
    )(keys, cu, cv)
