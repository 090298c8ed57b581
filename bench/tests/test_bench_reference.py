"""The plain reference, the comparison and the control's precision."""
import numpy as np
import pytest

from bench import graphgen, reference


def kruskal(src, dst, weight, n):
    """Kruskal's algorithm in plain Python, ties by edge id."""
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    mask = np.zeros(len(src), bool)
    for e in sorted(range(len(src)), key=lambda e: (weight[e], e)):
        a, b = find(int(src[e])), find(int(dst[e]))
        if a != b:
            parent[a] = b
            mask[e] = True
    return mask, [find(v) for v in range(n)]


def random_graph(rng, n, e, distinct_weights):
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    if distinct_weights:
        w = rng.random(e).astype(np.float32)
    else:
        w = rng.integers(0, 4, e).astype(np.float32)  # many ties
    return src, dst, w


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("distinct", [True, False])
def test_reference_matches_kruskal(seed, distinct):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 60))
    e = int(rng.integers(0, 150))
    src, dst, w = random_graph(rng, n, e, distinct)
    f = reference.minimum_spanning_forest(src, dst, w, n)
    mask, comp = kruskal(src, dst, w, n)
    np.testing.assert_array_equal(f.mask, mask)
    # Same partition into trees.
    pairs = set(zip(f.component.tolist(), comp))
    assert len(pairs) == len(set(comp)) == np.unique(f.component).size


def test_reference_component_is_a_parent_array():
    g = graphgen.generate_graph(3000, 6, np.random.default_rng(2))
    f = reference.minimum_spanning_forest(g.src, g.dst, g.weight, 3000)
    assert reference.compare(f.mask, f.component, f) == (0, 0)


def test_compare_counts_wrong_edges_and_bad_parents():
    g = graphgen.generate_graph(2000, 6, np.random.default_rng(4))
    f = reference.minimum_spanning_forest(g.src, g.dst, g.weight, 2000)
    flipped = f.mask.copy()
    flipped[[3, 17]] ^= True
    assert reference.compare(flipped, f.component, f) == (2, 0)
    assert reference.compare(f.mask[:-1], f.component, f) == (3000 * 2, 0)
    # Every vertex its own root: not the tree's root.
    ident = np.arange(2000)
    assert reference.compare(f.mask, ident, f) == (0, 1)
    # Two roots in one tree.
    two = f.component.copy()
    v = int(np.flatnonzero(two != np.arange(2000))[0])
    two[v] = v
    assert reference.compare(f.mask, two, f)[1] == 1
    assert reference.compare(f.mask, np.full(2000, -1), f)[1] == 1


def test_bfloat16_control_differs_at_cell_sizes():
    g = graphgen.generate_graph(10_000, 6, np.random.default_rng(5))
    f32 = reference.minimum_spanning_forest(g.src, g.dst, g.weight, 10_000)
    bf16 = reference.minimum_spanning_forest(g.src, g.dst, g.weight, 10_000,
                                             weight_dtype="bfloat16")
    wrong, bad = reference.compare(bf16.mask, bf16.component, f32)
    assert wrong > 0 and bad == 0
