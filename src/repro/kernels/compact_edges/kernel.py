"""Pallas TPU kernel: stream compaction of the Borůvka edge frontier.

Produces the stable live-prefix permutation ``compact_frontier`` consumes
(DESIGN.md §2b): live (non-covered) lane ids packed ascending from slot 0,
covered lane ids packed ascending after them.  Two sequential passes over
the covered bits, expressed as a 2-phase grid:

  * phase 0 streams the covered blocks and accumulates the live total —
    the dead cursor's start offset is not known until the whole stream has
    been counted;
  * phase 1 re-streams the blocks and assigns each lane its slot from two
    SMEM-resident cursors (live cursor from 0, dead cursor from the live
    total), writing into the VMEM-resident permutation.

TPU grid steps execute sequentially on a core, so the cursor read-modify-
write is race-free by construction — the same property the
``segment_min_edges`` scatter-min kernel leans on — and phase 0 fully
precedes phase 1 under row-major grid iteration.  The irregular per-lane
update runs on the scalar unit via fori_loop; the payload is one int32 per
lane, so the sweep is DMA-bound on the covered-bit stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(cov_ref, perm_ref, cnt_ref):
    phase = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when((phase == 0) & (blk == 0))
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    block = cov_ref.shape[0]

    @pl.when(phase == 0)
    def _count():
        # Live total accumulates in cnt[0] across the phase-0 sweep.
        alive = jnp.sum(1 - cov_ref[...]).astype(jnp.int32)
        cnt_ref[pl.ds(0, 1)] = cnt_ref[pl.ds(0, 1)] + alive

    @pl.when((phase == 1) & (blk == 0))
    def _cursors():
        # cnt[0] -> live cursor (restarts at 0), cnt[1] -> dead cursor
        # (starts at the live total counted in phase 0).
        live_total = cnt_ref[pl.ds(0, 1)]
        cnt_ref[pl.ds(1, 1)] = live_total
        cnt_ref[pl.ds(0, 1)] = jnp.zeros_like(live_total)

    @pl.when(phase == 1)
    def _assign():
        base = blk * block

        def body(i, _):
            dead = cov_ref[i]  # 0 = live -> cursor cnt[0], 1 -> cnt[1]
            slot = cnt_ref[pl.ds(dead, 1)]
            perm_ref[pl.ds(slot[0], 1)] = jnp.full((1,), base + i, jnp.int32)
            cnt_ref[pl.ds(dead, 1)] = slot + 1
            return 0

        jax.lax.fori_loop(0, block, body, 0)


def compact_edges_pallas(covered, block_edges: int = 4096,
                         interpret: bool = True):
    """covered: (E,) int32 {0,1} -> (perm (E,) int32, counts (2,) int32).

    E must be a multiple of block_edges (pad with covered=1).  After the
    call ``counts[0]`` is the live total (the live cursor's final value)
    and ``counts[1] == E``.  VMEM budget: block_edges*4B streamed +
    E*4B resident permutation.
    """
    e = covered.shape[0]
    assert e % block_edges == 0, (e, block_edges)
    grid = (2, e // block_edges)
    spec_cov = pl.BlockSpec((block_edges,), lambda p, i: (i,))
    spec_perm = pl.BlockSpec((e,), lambda p, i: (0,))
    spec_cnt = pl.BlockSpec((2,), lambda p, i: (0,))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec_cov],
        out_specs=(spec_perm, spec_cnt),
        out_shape=(jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)),
        interpret=interpret,
    )(covered)
