"""Public wrapper: padding, block selection, interpret switch.

``interpret`` defaults to auto-detection, like the other kernel packages:
compiled on TPU backends, interpreter mode everywhere else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret as _resolve_interpret
from repro.kernels.knn_graph.kernel import knn_graph_pallas


@functools.partial(jax.jit,
                   static_argnames=("k", "block_rows", "block_cols",
                                    "interpret"))
def knn_graph(points, *, k: int, block_rows: int = 128,
              block_cols: int = 128, interpret: bool | None = None):
    """(n, dim) f32 points -> (idx (n, k) int32, sqd (n, k) f32).

    Per row: the k nearest *other* points, ascending by (squared distance,
    point id) — deterministic under duplicate points.  Requires
    ``1 <= k <= n - 1`` (every row then has k finite candidates).  The point
    array is zero-padded to a multiple of both block sizes; pad cols are
    masked inside the kernel, pad rows are trimmed here.
    """
    n, _ = points.shape
    if not 1 <= k <= n - 1:
        raise ValueError(f"need 1 <= k <= n-1, got k={k} for n={n}")
    points = points.astype(jnp.float32)
    # The unrolled top-k merge keeps ~k live (block_rows, block_cols + k)
    # temporaries in VMEM; capping block_rows * k at 8192 keeps them in
    # the TPU's 16 MiB scoped VMEM at every escalated k (128 rows at the
    # default k, 64 rows at k = 128).
    rows_for_k = 1 << (max(1, 8192 // k).bit_length() - 1)  # pow2 floor
    br = min(block_rows, max(8, n), max(8, rows_for_k))
    bc = min(block_cols, max(8, n))
    step = math.lcm(br, bc)
    pad = (-n) % step
    if pad:
        points = jnp.concatenate(
            [points, jnp.zeros((pad, points.shape[1]), jnp.float32)])
    idx, sqd = knn_graph_pallas(points, k, n, block_rows=br, block_cols=bc,
                                interpret=_resolve_interpret(interpret))
    return idx[:n], sqd[:n]
