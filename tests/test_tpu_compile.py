"""Compile the main path's chip programs for a described TPU v5e.

Nothing runs: each test lowers a kernel or jitted step at the size the
chip smoke run uses and compiles it with the TPU compiler for a v5e chip
that is described, not attached.  That catches what interpret mode cannot
(unaligned VMEM slices, scoped-VMEM overflow, unsupported lowerings)
without a chip.  All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import candidate_min_edges
from repro.core.spmm_mst import spmm_candidates
from repro.graphs.csr_device import EllGraph, ell_width
from repro.kernels.knn_graph.ops import knn_graph

# Graph1M_6 (graphs/generator.PAPER_GRAPHS): V = 1M, E = V * 6 / 2.
V_1M, E_1M = 1_000_000, 3_000_000
CLUSTER_POINTS, CLUSTER_DIM = 4096, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k", [8, 16, 128])
def test_knn_graph_kernel_compiles_at_cluster_width(one_chip, k):
    """The clustering kernel at the default k, its first escalation, and
    the last k-doubling step (8 * 2**4), at the smoke run's 4096 points."""
    points = _shape(one_chip, (CLUSTER_POINTS, CLUSTER_DIM), jnp.float32)
    compiled = jax.jit(
        lambda p: knn_graph(p, k=k, interpret=False)).lower(points).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_single_engine_candidate_step_compiles_at_1m(one_chip):
    e = _shape(one_chip, (E_1M,))
    compiled = jax.jit(candidate_min_edges, static_argnames="num_nodes"
                       ).lower(e, e, e, num_nodes=V_1M).compile()
    assert compiled.memory_analysis() is not None


def test_spmm_default_candidate_step_compiles_at_1m(one_chip):
    """The spmm engine's default (jnp) candidate path at Graph1M_6's ELL
    width, with an overflow tail."""
    d = ell_width(2 * E_1M, V_1M)
    ovf = _shape(one_chip, (4096,))
    ell = EllGraph(_shape(one_chip, (V_1M, d)), _shape(one_chip, (V_1M, d)),
                   ovf, ovf, ovf)
    compiled = jax.jit(spmm_candidates).lower(
        ell, _shape(one_chip, (V_1M,))).compile()
    assert compiled.memory_analysis() is not None

