"""Plain reference for the benchmark's correctness check, in numpy alone.

``minimum_spanning_forest`` is Borůvka's algorithm written directly over
numpy arrays: every round each component takes its lightest outgoing edge,
components hook along those edges and are merged by pointer jumping.  Edges
are ordered by ``(weight, edge id)``, so equal weights are ordered by
position and the forest is unique.  It imports nothing of the program.

``weight_dtype`` rounds the weights before ordering them.  The benchmark's
control computes the forest with ``bfloat16`` weights, the precision below
the configuration's float32, and has to come out as not correct.
"""
from __future__ import annotations

from typing import NamedTuple

import ml_dtypes
import numpy as np

WEIGHT_DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


class Forest(NamedTuple):
    mask: np.ndarray        # (E,) bool, edges of the forest
    component: np.ndarray   # (V,) int64, one label per tree


def minimum_spanning_forest(src, dst, weight, num_nodes: int, *,
                            weight_dtype: str = "float32") -> Forest:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(weight, np.float32).astype(WEIGHT_DTYPES[weight_dtype])
    e = src.shape[0]
    order = np.argsort(w.astype(np.float32), kind="stable")
    rank = np.empty(e, np.int64)
    rank[order] = np.arange(e)

    comp = np.arange(num_nodes, dtype=np.int64)
    mask = np.zeros(e, bool)
    live = np.flatnonzero(src != dst)
    while True:
        cs, cd = comp[src[live]], comp[dst[live]]
        crossing = cs != cd
        live, cs, cd = live[crossing], cs[crossing], cd[crossing]
        if live.size == 0:
            return Forest(mask, comp)
        best = np.full(num_nodes, e, np.int64)
        r = rank[live]
        np.minimum.at(best, cs, r)
        np.minimum.at(best, cd, r)
        roots = np.flatnonzero(best < e)
        chosen = order[best[roots]]
        mask[chosen] = True
        a, b = comp[src[chosen]], comp[dst[chosen]]
        other = np.where(a == roots, b, a)
        hook = np.arange(num_nodes, dtype=np.int64)
        hook[roots] = other
        # Two components that chose the same edge point at each other: the
        # smaller label stays a root.
        mutual = (hook[other] == roots) & (roots < other)
        hook[roots[mutual]] = roots[mutual]
        while True:
            jumped = hook[hook]
            if np.array_equal(jumped, hook):
                break
            hook = jumped
        comp = hook[comp]


def compare(mask, parent, ref: Forest) -> tuple:
    """``(wrong_edges, bad_parent)`` of one answer against the reference.

    ``wrong_edges`` counts the edges whose membership in the forest
    differs (every edge, if the mask has the wrong length).  ``bad_parent``
    is 1 unless ``parent`` names, for each vertex, a root of the vertex's
    own tree that is its own parent, with one root per tree.
    """
    mask = np.asarray(mask)
    parent = np.asarray(parent)
    e, v = ref.mask.shape[0], ref.component.shape[0]
    wrong = (int(np.count_nonzero(mask != ref.mask))
             if mask.shape == (e,) else e)
    if parent.shape != (v,) or parent.min() < 0 or parent.max() >= v:
        return wrong, 1
    parent = parent.astype(np.int64)
    comp = ref.component
    ok = np.array_equal(parent[parent], parent)
    ok = ok and np.array_equal(comp[parent], comp)
    # One root per tree: the map tree -> root is a function.
    root_of = np.empty(v, np.int64)
    root_of[comp] = parent
    ok = ok and np.array_equal(root_of[comp], parent)
    return wrong, 0 if ok else 1
