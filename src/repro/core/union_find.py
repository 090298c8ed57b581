"""Union-find primitives ("find" / "components[]" of the paper).

Two flavours live here:

* Device-side pointer jumping (Shiloach-Vishkin shortcut): ``parent <-
  parent[parent]`` until fixpoint fully path-compresses every vertex in
  O(log depth) vector steps.  After each Borůvka round we compress to
  depth 1, so the per-round ``find`` is a single gather.  Each step makes
  one gather: the loop carries ``(parent, changed)``, the body sets
  ``changed`` from the gather it already made, and the loop test reads the
  flag.  A test that gathered ``parent[parent]`` itself would cost a
  second gather per step, and a third under ``jax.vmap``, whose
  ``while_loop`` batching rule evaluates ``cond`` again inside the body to
  pick which lanes advance.

* ``HostUnionFind``: the scalar numpy structure every host-side replay
  path shares — the Kruskal oracle (``core/oracle.py``), single-linkage
  dendrogram replay (``cluster/linkage.py``) and the dynamic-MSF layer
  (``dynamic/``).  Path halving + union by size, amortized near-O(1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class HostUnionFind:
    """Scalar union-find over vertex ids (host path).

    Path-halving ``find`` plus union-by-size keeps trees logarithmic, so
    per-op cost is inverse-Ackermann amortized.  ``components`` tracks the
    live component count so callers don't re-derive it.
    """

    __slots__ = ("parent", "size", "components")

    def __init__(self, n: int):
        self.parent = np.arange(n, dtype=np.int64)
        self.size = np.ones(n, dtype=np.int64)
        self.components = n

    def find(self, x: int) -> int:
        p = self.parent
        while p[x] != x:
            p[x] = p[p[x]]  # path halving
            x = p[x]
        return int(x)

    def union(self, a: int, b: int) -> bool:
        """Merge the components of ``a`` and ``b``; False if already one."""
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if self.size[ra] < self.size[rb]:
            ra, rb = rb, ra
        self.parent[rb] = ra
        self.size[ra] += self.size[rb]
        self.components -= 1
        return True

    def connected(self, a: int, b: int) -> bool:
        return self.find(a) == self.find(b)

    def size_of(self, x: int) -> int:
        """Size of ``x``'s component."""
        return int(self.size[self.find(x)])

    def roots(self) -> np.ndarray:
        """(V,) fully-compressed root array (vectorized pointer jumping)."""
        p = self.parent.copy()
        while True:
            pp = p[p]
            if np.array_equal(pp, p):
                return p
            p = pp


@jax.named_scope("mst.jump")
def pointer_jump(parent: jnp.ndarray) -> jnp.ndarray:
    """Fully path-compress ``parent`` so parent[v] is v's root for all v.

    One gather per step (module docstring); ``changed`` starts True, so
    an already compressed ``parent`` costs one gather.  Traced under the
    device phase name ``mst.jump`` (DESIGN.md §4); a jump inside a hooking
    wave keeps it, since the innermost scope wins.
    """

    def cond(carry):
        return carry[1]

    def body(carry):
        p, _ = carry
        q = p[p]
        return q, jnp.any(q != p)

    out, _ = jax.lax.while_loop(cond, body, (parent, jnp.asarray(True)))
    return out


def is_root(parent: jnp.ndarray) -> jnp.ndarray:
    """(V,) bool - vertex is the root of its component."""
    v = jnp.arange(parent.shape[0], dtype=parent.dtype)
    return parent == v


def count_components(parent: jnp.ndarray) -> jnp.ndarray:
    """Number of distinct components (requires compressed or any parent)."""
    return jnp.sum(is_root(pointer_jump(parent)).astype(jnp.int32))
