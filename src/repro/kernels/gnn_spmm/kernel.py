"""Pallas TPU kernels for semiring SpMV over an edge-slot stream.

One memory-access shape, two semirings (GGE-SpMM/FusedMM-style, adapted
to TPU):

  * the per-vertex accumulator stays VMEM-RESIDENT for the whole sweep
    (index_map pins block 0 every grid step) — the gather/scatter random
    access pattern that thrashes HBM on a mechanical port instead hits
    VMEM at register-adjacent latency;
  * the edge-slot list streams in blocks via BlockSpec (sequential DMA);
  * TPU grid steps execute sequentially on a core => the read-modify-write
    accumulation is race-free by construction.

``(+, *)`` — :func:`gather_segment_sum_pallas`, GNN message passing: each
slot moves a (d,)-row of node features, ``out[dst] += feat[src] * w``.
The inner loop is scalar-indexed but VECTOR-payload, so the VPU does
d-wide adds while the scalar unit chases indices.  Fusing
gather+scale+scatter-add means feat rows are read once per edge and
partial sums never visit HBM; the jnp reference materializes the (E, d)
message tensor.

``(min, filter)`` — :func:`gather_segment_min_pallas`, the Borůvka
candidate-selection semiring (DESIGN.md §2d): the payload is the packed
``(weight, edge_id)`` rank, the "multiply" is the cut filter
``label[row] != label[col]`` (an edge inside a component is a semiring
zero), and the reduction is scatter-min into the owning component's
accumulator row.  One sweep over the CSR/ELL slot stream replaces the
(E,)-wide segment_min scan of the edge-list engines.

Both kernels accumulate into a ``V+1``-row buffer: row ``num_nodes`` is a
sentinel row that absorbs padding slots (wrapper pads indices with
``num_nodes``, not 0), so padding can never alias a real vertex no matter
the semiring — see ``ops.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import INT_SENTINEL


def _sum_kernel(src_ref, dst_ref, w_ref, feat_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = src_ref.shape[0]

    def body(i, _):
        s = src_ref[i]
        d = dst_ref[i]
        w = w_ref[i]
        row = feat_ref[pl.ds(s, 1), :]
        out_ref[pl.ds(d, 1), :] = out_ref[pl.ds(d, 1), :] + row * w
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def gather_segment_sum_pallas(src, dst, w, feat, num_nodes: int,
                              block_edges: int = 2048,
                              interpret: bool = True):
    """src/dst (E,) int32, w (E,) float, feat (V+1, d) -> (V+1, d).

    E must be a multiple of block_edges; padding slots must aim ``dst`` at
    the sentinel row ``num_nodes`` (the wrapper slices it off).  ``feat``
    carries a matching sentinel row so padded ``src`` reads stay in
    bounds.
    """
    e = src.shape[0]
    v1, d = feat.shape
    assert e % block_edges == 0, (e, block_edges)
    assert v1 == num_nodes + 1, (v1, num_nodes)
    grid = (e // block_edges,)
    spec_e = pl.BlockSpec((block_edges,), lambda i: (i,))
    spec_feat = pl.BlockSpec((v1, d), lambda i: (0, 0))
    return pl.pallas_call(
        _sum_kernel,
        grid=grid,
        in_specs=[spec_e, spec_e, spec_e, spec_feat],
        out_specs=pl.BlockSpec((v1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((v1, d), feat.dtype),
        interpret=interpret,
    )(src, dst, w, feat)


def _min_kernel(row_ref, col_ref, key_ref, label_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, INT_SENTINEL)

    block = row_ref.shape[0]

    def body(i, _):
        r = row_ref[i]
        c = col_ref[i]
        k = key_ref[i]
        lr = label_ref[pl.ds(r, 1)]
        lc = label_ref[pl.ds(c, 1)]
        # Semiring "multiply": the cut filter.  An intra-component slot is
        # a semiring zero (sentinel key never wins the min).
        key = jnp.where(lr != lc, k, INT_SENTINEL)
        out_ref[pl.ds(lr[0], 1)] = jnp.minimum(out_ref[pl.ds(lr[0], 1)],
                                               key)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


def gather_segment_min_pallas(row, col, key, label, num_nodes: int,
                              block_edges: int = 4096,
                              interpret: bool = True):
    """row/col/key (E,) int32, label (V+1,) int32 -> (V+1,) int32.

    ``out[c] = min{ key[i] : label[row[i]] == c != label[col[i]] }`` with
    INT_SENTINEL identity — per-component minimum cut-edge rank, reduced
    at the slot's owning component.  E must be a multiple of block_edges;
    padding slots aim row == col == ``num_nodes`` at the sentinel label
    ``label[num_nodes] == num_nodes`` (self-labeled, so the filter kills
    them AND they land on the sentinel accumulator row).
    """
    e = row.shape[0]
    v1 = label.shape[0]
    assert e % block_edges == 0, (e, block_edges)
    assert v1 == num_nodes + 1, (v1, num_nodes)
    grid = (e // block_edges,)
    spec_e = pl.BlockSpec((block_edges,), lambda i: (i,))
    spec_v = pl.BlockSpec((v1,), lambda i: (0,))
    return pl.pallas_call(
        _min_kernel,
        grid=grid,
        in_specs=[spec_e, spec_e, spec_e, spec_v],
        out_specs=spec_v,
        out_shape=jax.ShapeDtypeStruct((v1,), jnp.int32),
        interpret=interpret,
    )(row, col, key, label)
