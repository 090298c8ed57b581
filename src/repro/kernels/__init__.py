"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has:
  kernel.py - pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  ops.py    - jit'd public wrapper (block-size selection, interpret switch)
  ref.py    - pure-jnp oracle used by the allclose test sweeps

On a TPU backend the kernels compile for the chip; elsewhere (the CPU test
suite) ``interpret`` defaults to True, which executes the kernel body in
Python while the BlockSpecs still encode the VMEM tiling the chip uses.
``tests/test_tpu_compile.py`` compiles the kernels on default chip paths
for a described TPU v5e.
"""
from repro.kernels.segment_min_edges.ops import (batched_segment_min_edges,
                                                 segment_min_edges)
from repro.kernels.compact_edges.ops import compact_edges
from repro.kernels.relabel_vertices.ops import relabel_vertices
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fm_interaction.ops import fm_interaction_kernel
from repro.kernels.gnn_spmm.ops import gather_segment_sum

__all__ = ["segment_min_edges", "batched_segment_min_edges", "compact_edges",
           "relabel_vertices", "flash_attention", "fm_interaction_kernel",
           "gather_segment_sum"]
