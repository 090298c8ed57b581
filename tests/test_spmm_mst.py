"""spmm engine (DESIGN.md §2d): semiring candidate selection + identity.

The conformance matrix (``tests/test_conformance.py``) already pins the
engine oracle-identical across variants/families/cadences; this module
pins the pieces underneath — the candidate SpMV itself against the
edge-list scan, layout refresh across epochs, overflow handling — and the
cross-engine round/wave identity that makes ``best``-vector equality an
engine contract rather than a coincidence.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.engine import candidate_min_edges, rank_edges_host
from repro.core.mst import minimum_spanning_forest
from repro.core.spmm_mst import (resolve_kernel, spmm_candidates,
                                 spmm_candidates_kernel, spmm_msf)
from repro.core.types import Graph, INT_SENTINEL
from repro.graphs.csr_device import ell_from_edges_host
from repro.graphs.generator import generate_graph

VARIANTS = ("cas", "lock")


def _mid_solve_parent(n, seed):
    """A non-trivial component labeling: hook each vertex to a random
    root, path-compressed (arbitrary labelings exercise the cut filter
    far harder than round-1 identity parents)."""
    rng = np.random.default_rng(seed)
    roots = rng.choice(n, size=max(2, n // 7), replace=False)
    lab = roots[rng.integers(0, roots.shape[0], n)]
    lab[roots] = roots
    return jnp.asarray(lab, jnp.int32)


@pytest.mark.parametrize("n,deg,seed", [(60, 4, 0), (200, 7, 1), (37, 2, 2)])
@pytest.mark.parametrize("width", [None, 4])
def test_spmm_candidates_match_edge_list_scan(n, deg, seed, width):
    """THE engine contract: the ELL(+overflow) semiring reduction returns
    the exact ``best`` vector of ``candidate_min_edges`` — same per-
    component key multisets, unique minima, so bitwise equality.  width=4
    forces a populated overflow tail."""
    g = generate_graph(n, deg, seed=seed)
    rank, _ = rank_edges_host(g.weight)
    ell = ell_from_edges_host(g.src, g.dst, rank, n, width=width)
    for pseed in range(3):
        parent = (jnp.arange(n, dtype=jnp.int32) if pseed == 0
                  else _mid_solve_parent(n, pseed))
        cu = parent[g.src]
        cv = parent[g.dst]
        key = jnp.where(cu == cv, INT_SENTINEL, rank)
        ref = candidate_min_edges(key, cu, cv, n)
        got = spmm_candidates(ell, parent)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_spmm_candidates_dead_lanes_excluded():
    """Sentinel-rank lanes (the packed spine's padding) must never produce
    a candidate — the builder drops them, so the reduction never sees
    them."""
    g = generate_graph(80, 5, seed=3)
    rank, _ = rank_edges_host(g.weight)
    kill = np.zeros(g.num_edges, bool)
    kill[::3] = True
    rk = jnp.where(jnp.asarray(kill), INT_SENTINEL, rank)
    ell = ell_from_edges_host(g.src, g.dst, rk, 80)
    parent = jnp.arange(80, dtype=jnp.int32)
    cu, cv = parent[g.src], parent[g.dst]
    key = jnp.where((cu == cv) | jnp.asarray(kill), INT_SENTINEL, rank)
    ref = candidate_min_edges(key, cu, cv, 80)
    np.testing.assert_array_equal(np.asarray(spmm_candidates(ell, parent)),
                                  np.asarray(ref))


@pytest.mark.parametrize("n,deg,seed", [(60, 4, 0), (37, 2, 2)])
@pytest.mark.parametrize("width", [None, 4])
def test_spmm_kernel_candidates_bit_identical(n, deg, seed, width):
    """The Pallas ``gather_segment_min`` route (PR 8 follow-up): the
    flattened ELL+overflow slot stream through the kernel must return
    the exact jnp-path ``best`` vector — empty-slot sentinels, overflow
    pads and all.  width=4 forces a populated overflow tail; off-TPU the
    kernel runs in interpret mode, same arithmetic."""
    g = generate_graph(n, deg, seed=seed)
    rank, _ = rank_edges_host(g.weight)
    ell = ell_from_edges_host(g.src, g.dst, rank, n, width=width)
    for pseed in range(3):
        parent = (jnp.arange(n, dtype=jnp.int32) if pseed == 0
                  else _mid_solve_parent(n, pseed))
        np.testing.assert_array_equal(
            np.asarray(spmm_candidates_kernel(ell, parent)),
            np.asarray(spmm_candidates(ell, parent)))


@pytest.mark.parametrize("variant", VARIANTS)
def test_spmm_kernel_full_solve_bit_identical(variant):
    """End-to-end backend gate check: ``kernel=True`` (interpret mode on
    CPU) solves bit-identically to the jnp path — mask, rounds, waves —
    in both the static-layout and epoch-loop drivers."""
    g = generate_graph(90, 4, seed=7)
    for kw in (dict(), dict(compaction=2)):
        ref = spmm_msf(g, variant=variant, kernel=False, **kw)
        got = spmm_msf(g, variant=variant, kernel=True, **kw)
        assert (np.asarray(got.mst_mask) == np.asarray(ref.mst_mask)).all()
        assert int(got.num_rounds) == int(ref.num_rounds)
        assert int(got.num_waves) == int(ref.num_waves)


def test_spmm_gate_takes_jnp_path_on_tpu(monkeypatch):
    """On a TPU backend the default candidate path is the jnp reduction:
    the chip's compiler refuses the Pallas kernel, so only an explicit
    ``kernel=True`` reaches it."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_kernel() is False
    assert resolve_kernel(None) is False
    assert resolve_kernel(True) is True


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kw", [dict(), dict(compaction=1),
                                dict(compaction=2),
                                dict(compaction=1, contraction=True),
                                dict(compaction=3, contraction=True)])
def test_spmm_round_structure_identical_to_single(variant, kw):
    """Not just the mask: rounds AND lock waves must match the single
    engine under every layout-maintenance config, because identical best
    vectors imply identical hooking decisions."""
    g = generate_graph(220, 5, seed=11)
    ref = minimum_spanning_forest(g, variant=variant)
    r = spmm_msf(g, variant=variant, **kw)
    assert (np.asarray(r.mst_mask) == np.asarray(ref.mst_mask)).all()
    assert int(r.num_rounds) == int(ref.num_rounds)
    assert int(r.num_waves) == int(ref.num_waves)
    assert int(r.num_components) == int(ref.num_components)


@pytest.mark.parametrize("variant", VARIANTS)
def test_spmm_star_graph_overflow_path(variant):
    """Hub degree >> ELL width: most hub slots live in the overflow tail,
    and the solve must still be exact (the lock variant's worst
    serialization shape, too)."""
    n = 300
    rng = np.random.default_rng(5)
    src = np.zeros(n - 1, np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    w = rng.random(n - 1).astype(np.float32)
    g = Graph(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
              num_nodes=n)
    for kw in (dict(), dict(compaction=1, contraction=True)):
        r = spmm_msf(g, variant=variant, **kw)
        assert int(r.num_components) == 1
        assert int(np.asarray(r.mst_mask).sum()) == n - 1
        np.testing.assert_allclose(float(r.total_weight), w.sum(),
                                   rtol=1e-5)


def test_spmm_disconnected_forest():
    n, k = 64, 32
    rng = np.random.default_rng(6)
    src = np.concatenate([np.arange(k - 1), np.arange(k, n - 1)])
    dst = src + 1
    w = rng.random(src.shape[0]).astype(np.float32)
    g = Graph(jnp.asarray(src.astype(np.int32)),
              jnp.asarray(dst.astype(np.int32)), jnp.asarray(w),
              num_nodes=n)
    for kw in (dict(), dict(compaction=2), dict(compaction=1,
                                                contraction=True)):
        r = spmm_msf(g, **kw)
        assert int(r.num_components) == 2
        assert int(np.asarray(r.mst_mask).sum()) == n - 2


def test_spmm_single_edge_and_isolated_vertices():
    g = Graph(jnp.asarray([0], jnp.int32), jnp.asarray([3], jnp.int32),
              jnp.asarray([0.5], jnp.float32), num_nodes=5)
    r = spmm_msf(g)
    assert int(r.num_components) == 4
    assert np.asarray(r.mst_mask).tolist() == [True]
    r2 = spmm_msf(g, compaction=1, contraction=True)
    assert int(r2.num_components) == 4
    assert np.asarray(r2.mst_mask).tolist() == [True]


def test_spmm_contraction_requires_compaction():
    g = generate_graph(32, 3, seed=0)
    with pytest.raises(ValueError, match="compaction"):
        spmm_msf(g, contraction=True)


@pytest.mark.parametrize("variant", VARIANTS)
def test_spmm_dense_graph_contraction(variant):
    """Dense class: many parallel supervertex pairs after a round or two —
    exercises the dedup + re-spread + ELL rebuild pipeline hard."""
    g = generate_graph(96, 24, seed=13)
    ref = minimum_spanning_forest(g, variant=variant, compaction=1,
                                  contraction=True)
    r = spmm_msf(g, variant=variant, compaction=1, contraction=True)
    assert (np.asarray(r.mst_mask) == np.asarray(ref.mst_mask)).all()
    assert int(r.num_rounds) == int(ref.num_rounds)
    assert int(r.num_waves) == int(ref.num_waves)
