"""The benchmark's copy of the paper's generator."""
import numpy as np
import pytest

from bench import graphgen, reference

TABLE_1 = [(f"Graph{label}_{deg}", n, deg)
           for label, n in [("10K", 10_000), ("100K", 100_000),
                            ("1M", 1_000_000)]
           for deg in (3, 6, 9)]


def test_copy_draws_what_the_program_generator_draws():
    from repro.graphs.generator import generate_graph

    for n, deg, seed in [(1000, 6, 0), (5000, 3, 7)]:
        ours = graphgen.generate_graph(n, deg, np.random.default_rng(seed))
        theirs = generate_graph(n, deg, seed=seed, as_jax=False)
        for a, b in zip(ours[:3], (theirs.src, theirs.dst, theirs.weight)):
            np.testing.assert_array_equal(a, b)


SPEC = {"num_nodes": 2000, "avg_degree": 6}


def test_pool_is_deterministic_per_seed():
    a = graphgen.generate_pool(SPEC, 3, 7, 2 ** 31 + 11)
    b = graphgen.generate_pool(SPEC, 3, 7, 2 ** 31 + 11)
    c = graphgen.generate_pool(SPEC, 3, 7, 2 ** 31 + 12)
    for x, y in zip(a, b):
        for u, v in zip(x[:3], y[:3]):
            np.testing.assert_array_equal(u, v)
    for x, y in zip(a, c):
        assert not np.array_equal(x.src, y.src)
        assert not np.array_equal(x.weight, y.weight)
    # Graphs of one pool differ from each other.
    assert not np.array_equal(np.sort(a[0].weight), np.sort(a[1].weight))


def test_seeds_relabel_the_same_graphs():
    """Every seed gets the base graphs with their edges relabelled: the
    same vertex ids and weighted edges, in another order and with their
    ends swapped at random; the same forest weight, another mask."""
    a = graphgen.generate_pool(SPEC, 2, 7, 1)
    b = graphgen.generate_pool(SPEC, 2, 7, 2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.sort(x.weight), np.sort(y.weight))
        fx = reference.minimum_spanning_forest(*x)
        fy = reference.minimum_spanning_forest(*y)
        assert fx.mask.sum() == fy.mask.sum() == x.num_nodes - 1
        assert np.sort(x.weight[fx.mask]).tolist() == \
            np.sort(y.weight[fy.mask]).tolist()
        assert not np.array_equal(fx.mask, fy.mask)
        assert not np.array_equal(x.src, y.src)
        # The same undirected weighted edges, vertex ids kept.
        edges = lambda g: sorted(zip(np.minimum(g.src, g.dst).tolist(),
                                     np.maximum(g.src, g.dst).tolist(),
                                     g.weight.tolist()))
        assert edges(x) == edges(y)


def test_pool_takes_its_classes_in_turn():
    classes = [{"num_nodes": 300, "avg_degree": 3},
               {"num_nodes": 500, "avg_degree": 9}]
    pool = graphgen.generate_pool(classes, 5, 7, 3)
    assert [(g.num_nodes, g.num_edges) for g in pool] == [
        (300, 450), (500, 2250), (300, 450), (500, 2250), (300, 450)]
    # One class as a dict is the same pool as a list of it.
    one = graphgen.generate_pool(classes[0], 2, 7, 3)
    listed = graphgen.generate_pool(classes[:1], 2, 7, 3)
    for x, y in zip(one, listed):
        np.testing.assert_array_equal(x.src, y.src)


@pytest.mark.parametrize("name,n,deg", TABLE_1)
def test_table_1_sizes(name, n, deg):
    g = graphgen.generate_graph(n, deg, np.random.default_rng(1))
    assert g.num_nodes == n
    assert g.num_edges == n * deg // 2
    assert g.src.dtype == g.dst.dtype == np.int32
    assert g.weight.dtype == np.float32
    assert not np.any(g.src == g.dst)
    assert 0 <= min(g.src.min(), g.dst.min())
    assert max(g.src.max(), g.dst.max()) < n


@pytest.mark.parametrize("n,deg", [(10_000, 3), (10_000, 6), (10_000, 9),
                                   (100_000, 6)])
def test_generated_graphs_are_connected(n, deg):
    g = graphgen.generate_graph(n, deg, np.random.default_rng(3))
    forest = reference.minimum_spanning_forest(g.src, g.dst, g.weight, n)
    assert np.unique(forest.component).size == 1
    assert forest.mask.sum() == n - 1
