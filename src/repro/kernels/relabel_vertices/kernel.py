"""Pallas TPU kernel: dense relabeling of surviving supervertex roots.

Contract-Borůvka (DESIGN.md §2c) ends each epoch by renaming the surviving
component roots to a dense ``[0, V')`` range so the next epoch's vertex
arrays can shrink to a smaller power-of-two bucket.  The renaming is a
*monotone* dense rank over the root indicator: root ``i`` gets id
``|{j < i : isroot[j]}|``, which preserves the relative order of root ids —
the property that keeps the CAS 2-cycle break ("smaller root survives") and
the lock arbitration ("min writer wins") making bit-identical decisions on
the contracted graph.

Same 2-phase count-then-assign grid as the ``compact_edges`` stream
compactor, with the cursor assigning *ranks* instead of permutation slots:

  * phase 0 streams the root-indicator blocks and accumulates the root
    total (the contracted vertex count V', needed by the caller to pick
    the next vertex bucket);
  * phase 1 re-streams the blocks and assigns each root the SMEM-resident
    cursor's current value, bumping it by one; non-root slots are written
    with INT_SENTINEL (they are never read through — every endpoint lookup
    goes ``new_id[parent[x]]`` and ``parent[x]`` is always a root — but a
    defined value keeps kernel == ref bit-exact).

TPU grid steps run sequentially on a core, so the cursor read-modify-write
is race-free by construction and phase 0 fully precedes phase 1 under
row-major iteration.  The per-slot update is scalar-unit fori_loop work;
the sweep is DMA-bound on the indicator stream, like the compactor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INT_SENTINEL = np.iinfo(np.int32).max


def _kernel(isroot_ref, newid_ref, cnt_ref):
    phase = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when((phase == 0) & (blk == 0))
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    block = isroot_ref.shape[0]

    @pl.when(phase == 0)
    def _count():
        # Root total accumulates in cnt[0] across the phase-0 sweep.
        roots = jnp.sum(isroot_ref[...]).astype(jnp.int32)
        cnt_ref[pl.ds(0, 1)] = cnt_ref[pl.ds(0, 1)] + roots

    @pl.when((phase == 1) & (blk == 0))
    def _cursor():
        # cnt[0] -> root total (phase-0 result), cnt[1] -> assign cursor.
        cnt_ref[pl.ds(1, 1)] = jnp.zeros((1,), jnp.int32)

    @pl.when(phase == 1)
    def _assign():
        base = blk * block

        def body(i, _):
            root = isroot_ref[i]
            cur = cnt_ref[pl.ds(1, 1)]
            val = jnp.where(root == 1, cur[0], INT_SENTINEL)
            newid_ref[pl.ds(base + i, 1)] = jnp.full((1,), val, jnp.int32)
            cnt_ref[pl.ds(1, 1)] = cur + root
            return 0

        jax.lax.fori_loop(0, block, body, 0)


def relabel_vertices_pallas(isroot, block_vertices: int = 4096,
                            interpret: bool = True):
    """isroot: (V,) int32 {0,1} -> (new_id (V,) int32, counts (2,) int32).

    V must be a multiple of block_vertices (pad with isroot=0).  After the
    call ``counts[0]`` is the root total V' and ``counts[1] == counts[0]``
    (the assign cursor's final value — the phase-1 sweep assigned exactly
    the roots phase 0 counted).  VMEM budget: block_vertices*4B streamed +
    V*4B resident new-id table.
    """
    v = isroot.shape[0]
    assert v % block_vertices == 0, (v, block_vertices)
    grid = (2, v // block_vertices)
    spec_root = pl.BlockSpec((block_vertices,), lambda p, i: (i,))
    spec_newid = pl.BlockSpec((v,), lambda p, i: (0,))
    spec_cnt = pl.BlockSpec((2,), lambda p, i: (0,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec_root],
        out_specs=(spec_newid, spec_cnt),
        out_shape=(jax.ShapeDtypeStruct((v,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)),
        interpret=interpret,
    )(isroot)
