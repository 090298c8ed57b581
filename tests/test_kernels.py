"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp ref oracles.

The tests run on the CPU, so every kernel runs in interpret mode; the
BlockSpecs still encode the TPU tiling, and ``test_tpu_compile.py``
compiles the chip-path kernels for a described v5e.  Tolerances: fp32 1e-5; bf16 inputs 2e-2 (per the public
FlashAttention/Triton test precedent).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import INT_SENTINEL
from repro.kernels.segment_min_edges.ops import (batched_segment_min_edges,
                                                 segment_min_edges,
                                                 sharded_segment_min_edges)
from repro.kernels.segment_min_edges.ref import (
    batched_segment_min_edges_ref, segment_min_edges_ref,
    sharded_segment_min_edges_ref)
from repro.kernels.compact_edges.ops import compact_edges
from repro.kernels.compact_edges.ref import compact_edges_ref
from repro.kernels.knn_graph.ops import knn_graph
from repro.kernels.knn_graph.ref import knn_graph_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.fm_interaction.ops import fm_interaction_kernel
from repro.kernels.fm_interaction.ref import fm_interaction_ref
from repro.kernels.gnn_spmm.ops import (gather_segment_min,
                                        gather_segment_sum)
from repro.kernels.gnn_spmm.ref import (gather_segment_min_ref,
                                        gather_segment_sum_ref)
from repro.kernels.relabel_vertices.ops import relabel_vertices
from repro.kernels.relabel_vertices.ref import relabel_vertices_ref


@pytest.mark.parametrize("v,e,block", [(17, 96, 32), (64, 512, 128),
                                       (200, 1000, 256), (5, 8, 256)])
def test_segment_min_sweep(v, e, block):
    key = jax.random.key(v * e)
    keys = jax.random.permutation(key, e).astype(jnp.int32)
    cu = jax.random.randint(key, (e,), 0, v, jnp.int32)
    cv = jax.random.randint(jax.random.key(e), (e,), 0, v, jnp.int32)
    out = segment_min_edges(keys, cu, cv, num_nodes=v, block_edges=block)
    ref = segment_min_edges_ref(keys, cu, cv, v)
    assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("e,block,frac", [(96, 32, 0.3), (512, 128, 0.7),
                                          (1000, 256, 0.5), (8, 256, 0.0),
                                          (300, 64, 1.0)])
def test_compact_edges_sweep(e, block, frac):
    """Stream-compaction kernel == jnp oracle: exact permutation + live
    count, across block splits, padding remainders, and covered densities
    (0.0 = nothing covered, 1.0 = everything)."""
    rng = np.random.default_rng(e + block)
    covered = jnp.asarray(rng.random(e) < frac)
    perm, live = compact_edges(covered, block_edges=block)
    rperm, rlive = compact_edges_ref(covered)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(rperm))
    assert int(live) == int(rlive)
    assert sorted(np.asarray(perm).tolist()) == list(range(e))


@pytest.mark.parametrize("v,block,frac", [(96, 256, 0.3), (512, 256, 0.7),
                                          (1000, 512, 0.5), (8, 256, 0.0),
                                          (300, 256, 1.0), (4096, 1024, 0.1)])
def test_relabel_vertices_sweep(v, block, frac):
    """Root-relabel kernel == jnp oracle: exact dense rank + root count,
    across block splits, padding remainders, and root densities (0.0 = no
    roots, 1.0 = every vertex is its own root — the first epoch)."""
    rng = np.random.default_rng(v + block)
    isroot = jnp.asarray(rng.random(v) < frac) if 0.0 < frac < 1.0 \
        else jnp.full((v,), bool(frac))
    nid, n = relabel_vertices(isroot, block_vertices=block)
    rnid, rn = relabel_vertices_ref(isroot)
    np.testing.assert_array_equal(np.asarray(nid), np.asarray(rnid))
    assert int(n) == int(rn) == int(np.asarray(isroot).sum())
    # The live half of the output is a monotone bijection onto [0, n):
    # order preservation is what keeps the contracted solve's min-root
    # arbitration identical to the uncontracted one.
    roots = np.asarray(nid)[np.asarray(isroot)]
    assert sorted(roots.tolist()) == list(range(int(n)))
    assert (np.diff(roots) > 0).all() if roots.size else True


# The acceptance contract for the clustering pipeline's kernel is
# bit-exactness vs the oracle (indices AND distances) — jit both sides so
# XLA applies the same fused-multiply-add contraction (see ref.py).
_knn_ref_jit = jax.jit(knn_graph_ref, static_argnums=1)


@pytest.mark.parametrize("n,d,k,br,bc", [
    (20, 2, 4, 8, 8),       # tiny, exact blocks
    (65, 3, 8, 16, 32),     # non-dividing n, mixed block sizes
    (128, 2, 5, 32, 32),    # dividing n
    (50, 8, 12, 64, 16),    # wide dim, block_rows > n
    (7, 2, 6, 8, 8),        # k == n - 1 (complete graph)
])
def test_knn_graph_sweep(n, d, k, br, bc):
    rng = np.random.default_rng(n * d + k)
    pts = rng.random((n, d)).astype(np.float32)
    idx, sqd = knn_graph(jnp.asarray(pts), k=k, block_rows=br, block_cols=bc)
    ridx, rsqd = _knn_ref_jit(jnp.asarray(pts), k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(sqd), np.asarray(rsqd))
    # Output contract: rows ascend by (distance, id), ids never self/pad.
    assert (np.diff(np.asarray(sqd), axis=1) >= 0).all()
    own = np.arange(n)[:, None]
    assert (np.asarray(idx) != own).all()
    assert (np.asarray(idx) < n).all()


def test_knn_graph_duplicate_points_tie_break():
    """Duplicate points tie at distance 0: the kernel must break ties by
    smallest point id, bit-identically to the oracle's stable sort."""
    base = np.random.default_rng(0).random((24, 2)).astype(np.float32)
    pts = np.repeat(base, 2, axis=0)  # every point twice
    idx, sqd = knn_graph(jnp.asarray(pts), k=4, block_rows=16, block_cols=16)
    ridx, rsqd = _knn_ref_jit(jnp.asarray(pts), 4)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(sqd), np.asarray(rsqd))
    # Each point's nearest neighbor is its duplicate partner at distance 0.
    pair = np.arange(48) ^ 1
    np.testing.assert_array_equal(np.asarray(idx)[:, 0], pair)
    assert (np.asarray(sqd)[:, 0] == 0).all()


def test_knn_graph_rejects_bad_k():
    pts = jnp.zeros((5, 2), jnp.float32)
    with pytest.raises(ValueError, match="1 <= k <= n-1"):
        knn_graph(pts, k=5)
    with pytest.raises(ValueError, match="1 <= k <= n-1"):
        knn_graph(pts, k=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,hkv,sq,skv,causal,window,cap", [
    (4, 4, 128, 128, True, None, None),
    (4, 2, 128, 128, True, None, None),      # GQA
    (2, 1, 64, 128, False, None, None),      # MQA, cross lengths
    (2, 2, 128, 128, True, 32, None),        # sliding window
    (2, 2, 64, 64, True, None, 30.0),        # softcap (gemma2)
])
def test_flash_attention_sweep(dtype, h, hkv, sq, skv, causal, window, cap):
    key = jax.random.key(h * sq)
    hd = 64
    q = jax.random.normal(key, (2, h, sq, hd), dtype)
    k = jax.random.normal(jax.random.key(1), (2, hkv, skv, hd), dtype)
    v = jax.random.normal(jax.random.key(2), (2, hkv, skv, hd), dtype)
    out = flash_attention(q, k, v, scale=hd ** -0.5, causal=causal,
                          window=window, cap=cap, block_q=32, block_kv=32)
    ref = flash_attention_ref(q, k, v, scale=hd ** -0.5, causal=causal,
                              window=window, cap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,f,k,block", [(64, 13, 10, 32), (100, 39, 10, 64),
                                         (8, 4, 16, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fm_interaction_sweep(b, f, k, block, dtype):
    v = jax.random.normal(jax.random.key(b), (b, f, k), dtype)
    out = fm_interaction_kernel(v, block_b=block)
    ref = fm_interaction_ref(v)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("v,e,d,block", [(32, 256, 16, 64), (100, 999, 8, 256),
                                         (64, 2048, 32, 512)])
def test_gnn_spmm_sweep(v, e, d, block):
    key = jax.random.key(v + e)
    src = jax.random.randint(key, (e,), 0, v, jnp.int32)
    dst = jax.random.randint(jax.random.key(1), (e,), 0, v, jnp.int32)
    w = jax.random.normal(jax.random.key(2), (e,))
    feat = jax.random.normal(jax.random.key(3), (v, d))
    out = gather_segment_sum(src, dst, w, feat, num_nodes=v,
                             block_edges=block)
    ref = gather_segment_sum_ref(src, dst, w, feat, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("v,e,block", [(32, 256, 64), (100, 999, 256),
                                       (17, 60, 4096), (64, 2048, 512)])
def test_gnn_spmm_min_semiring_sweep(v, e, block):
    """The (min, cut-filter) semiring path: kernel == jnp oracle over
    random slot streams and a random component labeling — including a
    non-divisible E (sentinel-row padding must be inert under min)."""
    keys = jax.random.permutation(jax.random.key(v + e), e).astype(jnp.int32)
    row = jax.random.randint(jax.random.key(1), (e,), 0, v, jnp.int32)
    col = jax.random.randint(jax.random.key(2), (e,), 0, v, jnp.int32)
    label = jax.random.randint(jax.random.key(3), (v,), 0, v, jnp.int32)
    out = gather_segment_min(row, col, keys, label, num_nodes=v,
                             block_edges=block)
    ref = gather_segment_min_ref(row, col, keys, label, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_gnn_spmm_padding_cannot_alias_real_rows():
    """The padding contract (sentinel-row dst, not w == 0): even when
    every real lane carries a NEGATIVE weight and vertex 0's feature is
    huge, padded lanes must contribute exactly zero to every row.  Under
    the old zeros-padding this held only because 0 * feat == 0 happened
    to be the sum identity; the min path has no such accident."""
    v, e, block = 8, 5, 4  # pad = 3 lanes
    src = jnp.zeros((e,), jnp.int32)
    dst = jnp.arange(e, dtype=jnp.int32)
    w = -jnp.ones((e,))
    feat = jnp.full((v, 3), 100.0).at[0].set(1e6)
    out = gather_segment_sum(src, dst, w, feat, num_nodes=v,
                             block_edges=block)
    ref = gather_segment_sum_ref(src, dst, w, feat, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    # Min path: INT_SENTINEL-key padding must not beat any real key, and
    # rows with no slots must report the sentinel (no candidate).
    keys = jnp.arange(e, dtype=jnp.int32) + 5
    label = jnp.arange(v, dtype=jnp.int32)
    mout = gather_segment_min(src, dst, keys, label, num_nodes=v,
                              block_edges=block)
    mref = gather_segment_min_ref(src, dst, keys, label, v)
    np.testing.assert_array_equal(np.asarray(mout), np.asarray(mref))
    assert int(mout[v - 1]) == INT_SENTINEL  # slotless row


@pytest.mark.parametrize("e", [1, 2, 3, 7])
def test_gnn_spmm_tiny_edge_counts(e):
    """Regression for the `min(block_edges, max(256, e))` clamp: a block
    larger than E made Pallas index maps step past the padded stream on
    tiny graphs.  The block must shrink to E, not grow past it."""
    v = 4
    src = jnp.arange(e, dtype=jnp.int32) % v
    dst = (jnp.arange(e, dtype=jnp.int32) + 1) % v
    w = jnp.ones((e,))
    feat = jnp.eye(v)
    out = gather_segment_sum(src, dst, w, feat, num_nodes=v)
    ref = gather_segment_sum_ref(src, dst, w, feat, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    keys = jnp.arange(e, dtype=jnp.int32)
    label = jnp.asarray([0, 0, 1, 1], jnp.int32)
    mout = gather_segment_min(src, dst, keys, label, num_nodes=v)
    mref = gather_segment_min_ref(src, dst, keys, label, v)
    np.testing.assert_array_equal(np.asarray(mout), np.asarray(mref))


@pytest.mark.parametrize("b,v,e,block", [(1, 17, 96, 32), (3, 64, 512, 128),
                                         (4, 40, 200, 256)])
def test_batched_segment_min_sweep(b, v, e, block):
    key = jax.random.key(b * v + e)
    keys = jnp.stack([
        jax.random.permutation(jax.random.fold_in(key, i), e)
        for i in range(b)]).astype(jnp.int32)
    cu = jax.random.randint(key, (b, e), 0, v, jnp.int32)
    cv = jax.random.randint(jax.random.key(e), (b, e), 0, v, jnp.int32)
    out = batched_segment_min_edges(keys, cu, cv, num_nodes=v,
                                    block_edges=block)
    ref = batched_segment_min_edges_ref(keys, cu, cv, v)
    assert out.shape == (b, v)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_batched_segment_min_matches_engine_padding():
    """Sentinel-rank padding contract: pad lanes (key=INT_SENTINEL,
    cu=cv=0) must never displace a real minimum."""
    from repro.core.types import INT_SENTINEL
    v, e = 32, 100
    keys = jax.random.permutation(jax.random.key(0), e).astype(jnp.int32)
    cu = jax.random.randint(jax.random.key(1), (e,), 0, v, jnp.int32)
    cv = jax.random.randint(jax.random.key(2), (e,), 0, v, jnp.int32)
    pad = jnp.full((28,), INT_SENTINEL, jnp.int32)
    keys2 = jnp.stack([jnp.concatenate([keys, pad]),
                       jnp.concatenate([pad, keys])])
    zeros = jnp.zeros((28,), jnp.int32)
    cu2 = jnp.stack([jnp.concatenate([cu, zeros]),
                     jnp.concatenate([zeros, cu])])
    cv2 = jnp.stack([jnp.concatenate([cv, zeros]),
                     jnp.concatenate([zeros, cv])])
    out = batched_segment_min_edges(keys2, cu2, cv2, num_nodes=v,
                                    block_edges=64)
    ref = segment_min_edges_ref(keys, cu, cv, v)
    assert (np.asarray(out[0]) == np.asarray(ref)).all()
    assert (np.asarray(out[1]) == np.asarray(ref)).all()


@pytest.mark.parametrize("v,e,shards,block", [(17, 96, 1, 32), (64, 512, 4, 64),
                                              (200, 1000, 8, 256),
                                              (40, 333, 7, 256)])
def test_sharded_segment_min_sweep(v, e, shards, block):
    """The shard-shaped grid is a layout, not a semantics change: output
    must equal the flat single-graph oracle for any shard count, including
    non-dividing E (sentinel pad)."""
    key = jax.random.key(v * e + shards)
    keys = jax.random.permutation(key, e).astype(jnp.int32)
    cu = jax.random.randint(key, (e,), 0, v, jnp.int32)
    cv = jax.random.randint(jax.random.key(e), (e,), 0, v, jnp.int32)
    out = sharded_segment_min_edges(keys, cu, cv, num_nodes=v,
                                    num_shards=shards, block_edges=block)
    ref = sharded_segment_min_edges_ref(keys, cu, cv, v)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_sharded_segment_min_matches_partition_layout():
    """Fed the exact per-shard rank tables the sharded engine ships to its
    mesh (graphs/partition_edges), the kernel must reproduce the global
    candidate search of round 1."""
    from repro.core.mst import rank_edges
    from repro.graphs.generator import generate_graph
    from repro.graphs.partition_edges import flatten_partition, \
        partition_edges

    g = generate_graph(300, 5, seed=9)
    v = g.num_nodes
    part = partition_edges(g, 4)
    s_src, s_dst, s_rank, _ = flatten_partition(part)
    out = sharded_segment_min_edges(s_rank, s_src, s_dst, num_nodes=v,
                                    num_shards=4, block_edges=256)
    rank, _ = rank_edges(g.weight)
    ref = segment_min_edges_ref(rank, g.src, g.dst, v)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_segment_min_inside_boruvka_round():
    """The kernel must be a drop-in for the engine's candidate search."""
    from repro.core.mst import rank_edges
    from repro.graphs.generator import generate_graph
    g = generate_graph(300, 5, seed=9)
    v = g.num_nodes
    rank, order = rank_edges(g.weight)
    parent = jnp.arange(v, dtype=jnp.int32)
    cu, cv = parent[g.src], parent[g.dst]
    out = segment_min_edges(rank, cu, cv, num_nodes=v, block_edges=256)
    ref = segment_min_edges_ref(rank, cu, cv, v)
    assert (np.asarray(out) == np.asarray(ref)).all()
