"""Pallas TPU kernel: blocked pairwise distances -> per-row top-k neighbors.

The Euclidean-MST clustering pipeline (DESIGN.md §3a) starts by turning an
``(n_points, dim)`` point cloud into a kNN candidate edge list.  The
all-pairs distance matrix is never materialized: the grid is
``(row_block, col_block)`` and each step computes one ``(block_rows,
block_cols)`` tile of squared distances, folding it into a VMEM-resident
running top-k per row (index_map pins the output row block across the col
sweep, exactly like ``segment_min_edges`` pins ``minimum[]``).

Distances use the expanded difference form ``sum((x - y)**2, axis=-1)``
rather than the Gram-matrix identity ``|x|^2 + |y|^2 - 2 x.y``: point-cloud
``dim`` is small so the op is DMA/VPU-bound either way, the difference form
cannot go negative under rounding, and — the contract this kernel is tested
against — it makes the tile values bit-identical to the one-shot ``ref.py``
oracle regardless of the block split.

Top-k merge: the running ``(block_rows, k)`` buffer is kept sorted by
``(distance, point_id)`` ascending.  Each tile concatenates buffer ‖ tile
columns and extracts k minima by repeated ``argmin`` + mask; ``argmin``
takes the *first* occurrence on ties, and the concat order (buffer ids <
tile ids, tile ids ascending) makes "first occurrence" equal "smallest
point id" — the same total order the oracle's stable sort produces, so
kernel == ref bit-exactly, including duplicate points (distance 0 ties).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INT_MAX = jnp.iinfo(jnp.int32).max

def _kernel(xr_ref, xc_ref, idx_ref, dist_ref, *, k: int, n_real: int,
            block_rows: int, block_cols: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    # Col axis restarts per row block => re-init this row block's top-k.
    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full_like(dist_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, n_real)

    x = xr_ref[...]  # (block_rows, dim)
    y = xc_ref[...]  # (block_cols, dim)
    sq = jnp.sum((x[:, None, :] - y[None, :, :]) ** 2, axis=-1)

    shape = (block_rows, block_cols)
    row_ids = (i * block_rows
               + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    col_ids = (j * block_cols
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    # Self-pairs and padded cols never become candidates.
    sq = jnp.where((row_ids == col_ids) | (col_ids >= n_real), jnp.inf, sq)

    cand_d = jnp.concatenate([dist_ref[...], sq], axis=1)
    cand_i = jnp.concatenate([idx_ref[...], col_ids], axis=1)

    new_d, new_i = [], []
    lane = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    for _ in range(k):
        m = jnp.argmin(cand_d, axis=1)  # first occurrence on ties
        new_d.append(jnp.min(cand_d, axis=1))
        hit = lane == m[:, None]
        # Exactly one lane per row hits, so a masked min selects its id.
        new_i.append(jnp.min(jnp.where(hit, cand_i, INT_MAX), axis=1))
        cand_d = jnp.where(hit, jnp.inf, cand_d)
    dist_ref[...] = jnp.stack(new_d, axis=1)
    idx_ref[...] = jnp.stack(new_i, axis=1)


def knn_graph_pallas(points, k: int, n_real: int,
                     block_rows: int = 128, block_cols: int = 128,
                     interpret: bool = True):
    """points: (N_pad, dim) f32 -> (idx (N_pad, k) int32, sqd (N_pad, k) f32).

    N_pad must be a multiple of both block_rows and block_cols (pad with
    zero points; cols >= n_real are masked, pad *rows* emit garbage the
    wrapper trims).  Per row, outputs are the k nearest real points != the
    row itself, sorted ascending by (squared distance, point id).
    VMEM budget per step: (block_rows + block_cols) * dim * 4B streamed +
    block_rows * k * 8B resident top-k.
    """
    n_pad, dim = points.shape
    assert n_pad % block_rows == 0, (n_pad, block_rows)
    assert n_pad % block_cols == 0, (n_pad, block_cols)
    grid = (n_pad // block_rows, n_pad // block_cols)
    kern = functools.partial(_kernel, k=k, n_real=n_real,
                             block_rows=block_rows, block_cols=block_cols)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, dim), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_cols, dim), lambda i, j: (j, 0))],
        out_specs=(pl.BlockSpec((block_rows, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_rows, k), lambda i, j: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((n_pad, k), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, k), jnp.float32)),
        interpret=interpret,
    )(points, points)
