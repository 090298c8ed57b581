"""Puts requests to ``MSTService``: a call submits its graphs, then flushes.

The service's responses are host arrays already; a request's answer is the
response the flush returned for it, served from the cache or solved.
"""
from __future__ import annotations

from bench.harness import Answer
from repro.core import SolveOptions
from repro.core.types import Graph
from repro.obs.trace import enable_annotations
from repro.serve.mst_service import MSTService


class Client:
    def __init__(self, config: dict, graphs, spans, *, annotate: bool):
        enable_annotations(annotate)
        self.service = MSTService(options=SolveOptions(**config["options"]),
                                  **config["service"])
        self.graphs = [Graph(g.src, g.dst, g.weight, num_nodes=g.num_nodes)
                       for g in graphs]
        self.spans = spans

    def call(self, indices):
        with self.spans("bench.submit"):
            for i in indices:
                self.service.submit(self.graphs[i])
        with self.spans("bench.flush"):
            responses = self.service.flush()
        return [Answer(r.mst_mask, r.parent, r.cached) for r in responses]

    def counters(self) -> dict:
        st = self.service.stats
        return {"pack_us": st.h_pack.sum, "hash_us": st.h_hash.sum,
                "trim_us": st.h_trim.sum, "flushes": st.flushes}

    def close(self) -> None:
        self.service.close()
        self.service = None
