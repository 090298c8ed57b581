"""The lock variant against an independent reference of its protocol.

``lock_boruvka`` below is paper §2.2.1 (lock-based UnionOfComponents) as
``engine.hook_lock_waves`` states it, written sequentially in numpy and
Python with nothing of the program's round code.  Each round takes every
component's lightest outgoing edge under the ``(weight, edge id)`` order of
``bench/reference.py``; then waves run until no component is waiting, or
for at most ``max_waves``.  In a wave each waiting component

  * re-finds both endpoints of its edge and goes on only if it is still a
    root, owns one endpoint and the other lies in another component;
  * writes its id into the lock cells of both components, the first
    writer in id order keeping a cell (the program's scatter-min);
  * is granted if it holds both cells, and then hooks the other component
    under itself and commits its edge;

and after the wave every vertex points at its root again.  Leftovers of a
capped round retry in the next one, which recomputes the minima.

The program must match it exactly: the forest, the parent array, the
rounds and the waves, per round as well as in total.  The reference also
checks that each wave's merges form a matching, no component in two.
"""
from typing import List, NamedTuple, Tuple

import numpy as np
import pytest

from bench.reference import minimum_spanning_forest
from repro.core import SolveOptions, make_solver
from repro.core.mst import round_trace
from repro.core.types import Graph
from repro.graphs.generator import generate_graph

MAX_WAVES = 16  # the program's default cap, which paper_1m6_lock runs


class LockRun(NamedTuple):
    mask: np.ndarray
    parent: np.ndarray
    round_waves: List[int]
    round_commits: List[int]    # edges in the forest after each round
    capped_rounds: int          # rounds that ended with a component waiting
    merges: List[List[Tuple[int, int]]]  # (winner, hooked) per wave

    @property
    def rounds(self) -> int:
        return len(self.round_waves)

    @property
    def waves(self) -> int:
        return sum(self.round_waves)


def lock_boruvka(src, dst, weight, num_nodes: int, *,
                 max_waves: int = MAX_WAVES) -> LockRun:
    src = [int(x) for x in np.asarray(src)]
    dst = [int(x) for x in np.asarray(dst)]
    e = len(src)
    order = np.argsort(np.asarray(weight, np.float32), kind="stable")
    rank = np.empty(e, np.int64)
    rank[order] = np.arange(e)

    parent = list(range(num_nodes))
    mask = np.zeros(e, bool)
    round_waves, round_commits, merges = [], [], []
    capped = 0

    def root(v):
        while parent[v] != v:
            v = parent[v]
        return v

    while True:
        best = {}
        for k in range(e):
            a, b = parent[src[k]], parent[dst[k]]
            if a != b:
                for c in (a, b):
                    if rank[k] < best.get(c, e):
                        best[c] = int(rank[k])
        if not best:
            break
        edge = {c: int(order[r]) for c, r in best.items()}
        waiting = sorted(edge)
        waves = 0
        while waiting and waves < max_waves:
            other = {}
            for r in waiting:
                cu, cv = parent[src[edge[r]]], parent[dst[edge[r]]]
                if parent[r] == r and cu != cv and r in (cu, cv):
                    other[r] = cv if cu == r else cu
            lock = {}
            for r in sorted(other):
                lock.setdefault(r, r)
                lock.setdefault(other[r], r)
            granted = [r for r in sorted(other)
                       if lock[r] == r and lock[other[r]] == r]
            touched = [c for r in granted for c in (r, other[r])]
            assert len(set(touched)) == len(touched), "not a matching"
            for r in granted:
                parent[other[r]] = r
                mask[edge[r]] = True
            merges.append([(r, other[r]) for r in granted])
            parent = [root(v) for v in range(num_nodes)]
            waiting = [r for r in sorted(other) if r not in set(granted)]
            waves += 1
        capped += bool(waiting)
        round_waves.append(waves)
        round_commits.append(int(mask.sum()))
    return LockRun(mask, np.asarray(parent), round_waves, round_commits,
                   capped, merges)


def _graph(src, dst, weight, num_nodes) -> Graph:
    return Graph(np.asarray(src, np.int32), np.asarray(dst, np.int32),
                 np.asarray(weight, np.float32), num_nodes=num_nodes)


def _path(n, seed):
    rng = np.random.default_rng(seed)
    v = np.arange(n - 1)
    return _graph(v, v + 1, rng.random(n - 1), n)


def _star(leaves, seed):
    rng = np.random.default_rng(seed)
    return _graph(np.zeros(leaves, int), np.arange(1, leaves + 1),
                  rng.random(leaves), leaves + 1)


def _forest(seed):
    """Three random components side by side, and isolated vertices."""
    parts = [generate_graph(n, 3, seed=seed + i, as_jax=False)
             for i, n in enumerate((60, 90, 40))]
    src, dst, w, base = [], [], [], 0
    for g in parts:
        src.append(np.asarray(g.src) + base)
        dst.append(np.asarray(g.dst) + base)
        w.append(np.asarray(g.weight))
        base += g.num_nodes
    return _graph(np.concatenate(src), np.concatenate(dst),
                  np.concatenate(w), base + 7)


def _ties(seed):
    """Three weights for every edge: the edge id orders the ties."""
    g = generate_graph(150, 6, seed=seed, as_jax=False)
    w = np.random.default_rng(seed).integers(1, 4, g.num_edges) / 4
    return _graph(g.src, g.dst, w, g.num_nodes)


CASES = {
    **{f"random_{s}": (lambda s=s: generate_graph(300, 6, seed=s,
                                                  as_jax=False))
       for s in (0, 1, 2, 3)},
    "random_sparse": lambda: generate_graph(400, 3, seed=11, as_jax=False),
    "path": lambda: _path(200, 5),
    "star": lambda: _star(12, 6),
    "star_capped": lambda: _star(40, 7),
    "forest": lambda: _forest(20),
    "ties": lambda: _ties(8),
}


@pytest.fixture(scope="module")
def solver():
    return make_solver(SolveOptions(engine="single", variant="lock"))


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_the_protocol(case, solver):
    g = CASES[case]()
    ref = lock_boruvka(g.src, g.dst, g.weight, g.num_nodes)
    # The protocol's forest is the exact one.
    exact = minimum_spanning_forest(g.src, g.dst, g.weight, g.num_nodes)
    np.testing.assert_array_equal(ref.mask, exact.mask)

    got = solver.solve(g)
    np.testing.assert_array_equal(np.asarray(got.mst_mask), ref.mask)
    np.testing.assert_array_equal(np.asarray(got.parent), ref.parent)
    assert int(got.num_rounds) == ref.rounds
    assert int(got.num_waves) == ref.waves
    assert solver.last_trace.num_rounds == ref.rounds
    assert solver.last_trace.num_waves == ref.waves

    per_round = round_trace(g, variant="lock")
    assert per_round.waves == list(np.cumsum(ref.round_waves))
    assert per_round.commits == ref.round_commits


def test_cap_binds_and_leftovers_retry_next_round(solver):
    """On a star every wave's lock on the centre admits one merge, so 40
    leaves need more than one round of 16 waves."""
    g = _star(40, 7)
    ref = lock_boruvka(g.src, g.dst, g.weight, g.num_nodes)
    assert ref.capped_rounds >= 2
    assert ref.round_waves[:2] == [MAX_WAVES, MAX_WAVES]
    assert all(len(m) == 1 for m in ref.merges)
    got = solver.solve(g)
    assert int(got.num_rounds) == ref.rounds >= 3
    assert int(got.num_waves) == ref.waves == 40
    np.testing.assert_array_equal(np.asarray(got.mst_mask), ref.mask)


def test_uncapped_reference_needs_fewer_rounds():
    """Without the cap the star's waves all fall in one round: the cap
    moves work between rounds, and the forest stays the same."""
    g = _star(40, 7)
    capped = lock_boruvka(g.src, g.dst, g.weight, g.num_nodes)
    free = lock_boruvka(g.src, g.dst, g.weight, g.num_nodes, max_waves=10**6)
    assert free.rounds == 1 and free.capped_rounds == 0
    assert capped.rounds > free.rounds
    np.testing.assert_array_equal(free.mask, capped.mask)

