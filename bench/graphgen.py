"""The paper's graph generator (arXiv:2005.06913 §3), kept with the benchmark.

A copy of ``repro.graphs.generator.generate_graph`` that returns numpy
arrays, so the benchmark's inputs cannot move when the program's generator
changes.  A random spanning tree (uniform attachment under a random
relabelling) makes the graph connected; uniform random extra edges with no
self loops raise the average degree to the target, E = V * degree / 2.
Weights are uniform in [0, 1), jittered by edge index and stored as
float32, so equal weights remain possible and are ordered by edge id.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class EdgeList(NamedTuple):
    src: np.ndarray     # (E,) int32
    dst: np.ndarray     # (E,) int32
    weight: np.ndarray  # (E,) float32
    num_nodes: int

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def generate_graph(num_nodes: int, avg_degree: float,
                   rng: np.random.Generator) -> EdgeList:
    """Connected random graph with mean degree ``avg_degree``, drawn from
    ``rng`` exactly as the paper's generator draws it from its seed."""
    n = int(num_nodes)
    num_edges = max(n - 1, int(round(n * avg_degree / 2)))

    perm = rng.permutation(n).astype(np.int64)
    attach = (rng.random(n - 1) * np.arange(1, n)).astype(np.int64)
    tree_src = perm[attach]
    tree_dst = perm[1:]

    extra = num_edges - (n - 1)
    if extra > 0:
        a = rng.integers(0, n, size=extra, dtype=np.int64)
        b = rng.integers(0, n - 1, size=extra, dtype=np.int64)
        b = np.where(b >= a, b + 1, b)
        src = np.concatenate([tree_src, a])
        dst = np.concatenate([tree_dst, b])
    else:
        src, dst = tree_src, tree_dst

    weight = rng.random(src.shape[0]).astype(np.float64)
    weight = (weight + np.arange(src.shape[0]) * 1e-12).astype(np.float32)
    return EdgeList(src.astype(np.int32), dst.astype(np.int32), weight, n)


def relabel(g: EdgeList, rng: np.random.Generator) -> EdgeList:
    """The same graph with its edges relabelled: a new edge order, each
    edge's ends in a random order, the vertex ids kept.  The forest's
    weight, the rounds and the work are those of ``g`` (up to the order of
    equal weights, which follows the edge ids); the arrays and the forest's
    mask are others."""
    order = rng.permutation(g.num_edges)
    src, dst = g.src[order], g.dst[order]
    swap = rng.random(g.num_edges) < 0.5
    return EdgeList(np.where(swap, dst, src), np.where(swap, src, dst),
                    g.weight[order], g.num_nodes)


def generate_pool(classes, count: int, base_seed: int, seed) -> list:
    """``count`` graphs, entry ``i`` of class ``classes[i % len(classes)]``
    (a class is a dict with ``num_nodes`` and ``avg_degree``; a single
    dict is one class).

    The graphs themselves come from ``base_seed``, the same for every run
    of a cell, so every seed does the same work.  ``seed`` (a whole number
    or a ``SeedSequence``) relabels each one's edges: the same seed gives
    the same pool, and every seed gives other arrays and other answers.
    """
    if isinstance(classes, dict):
        classes = [classes]
    if not isinstance(seed, np.random.SeedSequence):
        seed = np.random.SeedSequence(seed)
    bases = np.random.SeedSequence(base_seed).spawn(count)
    out = []
    for i, (b, s) in enumerate(zip(bases, seed.spawn(count))):
        c = classes[i % len(classes)]
        out.append(relabel(generate_graph(c["num_nodes"], c["avg_degree"],
                                          np.random.default_rng(b)),
                           np.random.default_rng(s)))
    return out
