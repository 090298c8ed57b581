"""MST core: every variant vs the Kruskal oracle + property tests."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.mst import (minimum_spanning_forest, mst_optimized,
                            mst_unoptimized, rank_edges)
from repro.core.oracle import kruskal_numpy
from repro.core.types import Graph
from repro.core.union_find import count_components, pointer_jump
from repro.core.coarsen import boruvka_coarsen, coarsen_edges, \
    coarsen_features
from repro.core.partition import mst_partition
from repro.graphs.generator import generate_graph


def _check(result, graph, oracle_mask, oracle_total):
    mask = np.asarray(result.mst_mask)
    # distinct-rank construction => unique MSF => exact edge-set match
    assert (mask == oracle_mask).all()
    assert np.isclose(float(result.total_weight), oracle_total, rtol=1e-5)
    assert int(result.num_components) == 1
    assert mask.sum() == graph.num_nodes - 1


@pytest.mark.parametrize("n,deg,seed", [(60, 3, 0), (300, 6, 1),
                                        (1000, 4, 2)])
@pytest.mark.parametrize("variant", ["cas", "lock"])
def test_variants_match_oracle(n, deg, seed, variant):
    g = generate_graph(n, deg, seed=seed)
    om, ow, _ = kruskal_numpy(g.src, g.dst, g.weight, g.num_nodes)
    r = minimum_spanning_forest(g, variant=variant)
    _check(r, g, om, ow)


@pytest.mark.parametrize("fn", [mst_unoptimized, mst_optimized])
def test_sequential_baselines(fn):
    g = generate_graph(250, 5, seed=3)
    om, ow, _ = kruskal_numpy(g.src, g.dst, g.weight, g.num_nodes)
    r = fn(g)
    _check(r, g, om, ow)


def test_lock_and_cas_same_tree_different_waves():
    g = generate_graph(500, 6, seed=4)
    r_cas = minimum_spanning_forest(g, variant="cas")
    r_lock = minimum_spanning_forest(g, variant="lock")
    assert (np.asarray(r_cas.mst_mask) == np.asarray(r_lock.mst_mask)).all()
    # The lock protocol serializes: strictly more waves than CAS rounds.
    assert int(r_lock.num_waves) > int(r_cas.num_waves)


def test_duplicate_weights_handled():
    # Paper assumes distinct weights; our rank construction removes the
    # assumption - duplicate weights must still give a valid MSF whose
    # total weight matches the oracle's.
    g = generate_graph(200, 4, seed=5)
    w = jnp.round(g.weight * 8) / 8.0  # heavy ties
    g = Graph(g.src, g.dst, w, num_nodes=g.num_nodes)
    om, ow, _ = kruskal_numpy(g.src, g.dst, g.weight, g.num_nodes)
    r = minimum_spanning_forest(g)
    assert (np.asarray(r.mst_mask) == om).all()


def test_unsized_graph_needs_num_nodes():
    """A legacy unsized Graph must fail loudly without a vertex count, and
    solve identically when one is attached either way."""
    g = generate_graph(80, 4, seed=12)
    legacy = Graph(g.src, g.dst, g.weight)  # unsized
    with pytest.raises(ValueError, match="num_nodes"):
        minimum_spanning_forest(legacy)
    r0 = minimum_spanning_forest(legacy, num_nodes=g.num_nodes)
    r1 = minimum_spanning_forest(g)
    assert (np.asarray(r0.mst_mask) == np.asarray(r1.mst_mask)).all()


def test_rank_edges_bijection():
    g = generate_graph(100, 5, seed=6)
    rank, order = rank_edges(g.weight)
    e = g.num_edges
    assert sorted(np.asarray(rank).tolist()) == list(range(e))
    assert (np.asarray(order[rank]) == np.arange(e)).all()


def test_pointer_jump_full_compression():
    # chain 0->1->2->3 (root 3); singleton 4; pair 6->5 (root 5)
    parent = jnp.asarray([1, 2, 3, 3, 4, 5, 5])
    c = pointer_jump(parent)
    assert (np.asarray(c) == np.asarray([3, 3, 3, 3, 4, 5, 5])).all()
    assert int(count_components(parent)) == 3


def _numpy_roots(parent):
    p = np.asarray(parent)
    while True:
        q = np.take_along_axis(p, p, axis=-1)
        if np.array_equal(q, p):
            return p
        p = q


def _random_forest(n, seed, reach):
    """Parent array of a random forest: vertices in a random order, each
    pointing ``reach`` or fewer places back (a root when it draws itself)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    pos = np.arange(n)
    back = np.minimum(rng.integers(0, reach + 1, size=n), pos)
    parent = np.empty(n, np.int32)
    parent[perm] = perm[pos - back]
    return parent


_CHAIN = np.minimum(np.arange(1, 2 ** 12 + 1), 2 ** 12 - 1).astype(np.int32)


@pytest.mark.parametrize("case", [
    "identity", "star", "chain", "forest_shallow", "forest_deep",
    "vmap_done_and_chain"])
def test_pointer_jump_matches_numpy_fixpoint(case):
    """Full compression equals the numpy fixpoint ``p = p[p]``, plain and
    under vmap, where a lane that is already compressed rides along
    unchanged while another lane still jumps."""
    n = 2 ** 12
    parents = {
        "identity": np.arange(n, dtype=np.int32),
        "star": np.zeros(n, np.int32),
        "chain": _CHAIN,
        "forest_shallow": _random_forest(n, seed=0, reach=n),
        "forest_deep": _random_forest(n, seed=1, reach=3),
        "vmap_done_and_chain": np.stack([
            np.arange(n, dtype=np.int32), _CHAIN, np.zeros(n, np.int32),
            _random_forest(n, seed=2, reach=2)]),
    }
    parent = parents[case]
    jump = jax.vmap(pointer_jump) if parent.ndim == 2 else pointer_jump
    got = np.asarray(jax.jit(jump)(jnp.asarray(parent)))
    assert got.dtype == parent.dtype
    np.testing.assert_array_equal(got, _numpy_roots(parent))


@pytest.mark.parametrize("batched", [False, True])
def test_pointer_jump_one_gather_per_step(batched):
    """The loop carries its convergence flag: the lowered program holds one
    gather, plain and under vmap (whose while_loop batching rule would
    evaluate a gathering ``cond`` again inside the body)."""
    if batched:
        jump, x = jax.vmap(pointer_jump), jnp.zeros((64, 16384), jnp.int32)
    else:
        jump, x = pointer_jump, jnp.arange(4096, dtype=jnp.int32)
    hlo = jax.jit(jump).lower(x).as_text(dialect="hlo")
    assert hlo.count(" gather(") == 1


def test_coarsening_merges_and_pools():
    g = generate_graph(400, 5, seed=7)
    v = g.num_nodes
    c = boruvka_coarsen(g, num_nodes=v, num_rounds=1)
    nc = int(c.num_clusters)
    assert 1 <= nc < v
    cl = np.asarray(c.cluster)
    assert cl.min() == 0 and cl.max() == nc - 1
    feats = jnp.ones((v, 4))
    pooled = coarsen_features(feats, c, num_clusters=v)
    assert np.allclose(np.asarray(pooled[:nc]), 1.0)
    cu, cv, m = coarsen_edges(g, c)
    # intra-cluster edges masked out
    assert (np.asarray(cu)[np.asarray(m)] !=
            np.asarray(cv)[np.asarray(m)]).all()


def test_mst_partition_covers_all_nodes():
    g = generate_graph(300, 4, seed=8)
    v = g.num_nodes
    part, sizes = mst_partition(g.src, g.dst, g.weight, v, 4)
    assert part.shape == (v,)
    assert sizes.sum() == v
    assert (part >= 0).all() and (part < 4).all()


def test_round_trace_nonconvergence_diagnostic(monkeypatch):
    """When hooking cycles (done never flips), round_trace must abort with
    a diagnostic carrying the round count, graph size, variant and the
    live-edge tail — not loop forever or fail bare."""
    from repro.core import mst as mst_mod

    g = generate_graph(6, 3, seed=0)

    def stuck(state, *args, **kwargs):
        return state._replace(done=jnp.asarray(False))

    monkeypatch.setattr(mst_mod, "_one_round_jit", stuck)
    with pytest.raises(RuntimeError,
                       match=r"failed to converge: \d+ rounds exceed "
                             r"num_nodes=6 \(variant='cas'\); "
                             r"live edges over the last rounds"):
        mst_mod.round_trace(g)
