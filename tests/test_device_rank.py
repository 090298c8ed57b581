"""The single engine's in-program edge rank against its host rank.

On an accelerator ``minimum_spanning_forest`` ranks the edges inside its
jitted program (``rank_edges``, device phase ``mst.sort``); on the CPU it
keeps numpy's host rank (``rank_edges_host``).  ``mst._host_ranking`` makes
that choice from the platform probe ``mst._platform``.  These tests steer
the probe to run the in-program path here, and require it to give the
host-ranked forest bit for bit: mask, parent, rounds, waves and weight.
"""
import numpy as np
import pytest

import repro.core.mst as mst
from repro.core.mst import minimum_spanning_forest
from repro.core.types import Graph
from repro.graphs.generator import generate_graph
from repro.obs.trace import collect_phases


def _graph(src, dst, weight, num_nodes):
    return Graph(np.asarray(src, np.int32), np.asarray(dst, np.int32),
                 np.asarray(weight, np.float32), num_nodes=num_nodes)


def heavy_ties():
    """Weights from three values, so nearly every comparison is a tie
    broken by edge id."""
    g = generate_graph(300, 6, seed=11)
    w = np.random.default_rng(11).integers(0, 3, g.num_edges)
    return _graph(g.src, g.dst, w, g.num_nodes)


def signed_zeros():
    """Half the weights -0.0, half +0.0, interleaved: the two zeros must
    tie, as numpy's sort has them, and not order -0.0 first."""
    g = generate_graph(200, 4, seed=12)
    w = np.where(np.arange(g.num_edges) % 2 == 0, -0.0, 0.0)
    w[::7] = np.random.default_rng(12).normal(size=w[::7].shape)
    return _graph(g.src, g.dst, w, g.num_nodes)


def odd_edge_count():
    g = generate_graph(257, 3, seed=13)
    e = g.num_edges - (1 - g.num_edges % 2)
    return _graph(np.asarray(g.src)[:e], np.asarray(g.dst)[:e],
                  np.asarray(g.weight)[:e], g.num_nodes)


def single_edge():
    return _graph([0], [1], [2.5], 2)


def disconnected_forest():
    """Two components and isolated vertices: a forest, not a tree."""
    a = generate_graph(120, 4, seed=14)
    b = generate_graph(80, 4, seed=15)
    src = np.concatenate([np.asarray(a.src), np.asarray(b.src) + 120])
    dst = np.concatenate([np.asarray(a.dst), np.asarray(b.dst) + 120])
    w = np.concatenate([np.asarray(a.weight), np.asarray(b.weight)])
    return _graph(src, dst, w, 120 + 80 + 5)


GRAPHS = {f.__name__: f for f in (heavy_ties, signed_zeros, odd_edge_count,
                                  single_edge, disconnected_forest)}
SOLVES = {
    "plain": dict(compaction=0),
    "compaction": dict(compaction=2),
    "contraction": dict(compaction=2, contraction=True),
}


def _solve(graph, platform, monkeypatch, **kwargs):
    monkeypatch.setattr(mst, "_platform", lambda: platform)
    with collect_phases() as phases:
        r = minimum_spanning_forest(graph, **kwargs)
        out = {k: np.asarray(getattr(r, k)) for k in
               ("mst_mask", "parent", "num_rounds", "num_waves",
                "total_weight", "num_components")}
    return out, dict(phases)


@pytest.mark.parametrize("solve", sorted(SOLVES))
@pytest.mark.parametrize("variant", ["cas", "lock"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_in_program_rank_gives_the_host_ranked_forest(graph, variant, solve,
                                                      monkeypatch):
    g = GRAPHS[graph]()
    kwargs = dict(SOLVES[solve], variant=variant)
    host, host_phases = _solve(g, "cpu", monkeypatch, **kwargs)
    dev, dev_phases = _solve(g, "tpu", monkeypatch, **kwargs)
    for key in host:
        np.testing.assert_array_equal(dev[key], host[key], err_msg=key)
    # The host rank ran on the CPU path alone.
    assert "rank" in host_phases
    assert "rank" not in dev_phases


@pytest.mark.parametrize("platform,on_host", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_host_ranking_only_on_the_cpu(platform, on_host, monkeypatch):
    monkeypatch.setattr(mst, "_platform", lambda: platform)
    w = np.asarray([3.0, 1.0, 1.0, -0.0, 0.0], np.float32)
    rank, order = mst._host_ranking(w)
    if on_host:
        np.testing.assert_array_equal(np.asarray(order), [3, 4, 1, 2, 0])
        np.testing.assert_array_equal(np.asarray(rank), [4, 2, 3, 0, 1])
    else:
        assert rank is None and order is None


def test_platform_probe_reads_the_default_backend():
    import jax

    assert mst._platform() == jax.default_backend()
