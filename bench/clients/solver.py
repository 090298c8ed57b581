"""Puts requests to the planned solver: ``make_solver(...).solve(graph)``.

One request is one graph, handed over as the numpy arrays a user's loader
makes.  A solve ends when the forest's mask and parent are on the host.
"""
from __future__ import annotations

import numpy as np

from bench.harness import Answer
from repro.core import SolveOptions, make_solver
from repro.core.types import Graph
from repro.obs.trace import enable_annotations


class Client:
    def __init__(self, config: dict, graphs, spans, *, annotate: bool):
        enable_annotations(annotate)
        self.solver = make_solver(SolveOptions(**config["options"]))
        self.graphs = [Graph(g.src, g.dst, g.weight, num_nodes=g.num_nodes)
                       for g in graphs]
        self.spans = spans
        self.rank_us = 0.0

    def call(self, indices):
        out = []
        for i in indices:
            with self.spans("bench.solve"):
                r = self.solver.solve(self.graphs[i])
                out.append(Answer(np.asarray(r.mst_mask),
                                  np.asarray(r.parent), False))
            self.rank_us += self.solver.last_trace.rank_us
        return out

    def counters(self) -> dict:
        return {"rank_us": self.rank_us, "solves": self.solver.stats.solves}

    def close(self) -> None:
        self.solver = None
