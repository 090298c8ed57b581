"""The least work any exact minimum spanning forest has to do.

Whatever implements the solve, it has to read each edge's source,
destination and weight once (4 + 4 + 4 bytes), write the forest's mask
once (1 byte per edge) and write each vertex's parent once (4 bytes).  The
count depends on the graph's true edges and vertices alone, never on
padded shapes, scan buckets or rounds.  It does no arithmetic worth
counting, so the bound is the chip's memory bandwidth.
"""
from __future__ import annotations

EDGE_READ_BYTES = 4 + 4 + 4
MASK_WRITE_BYTES = 1
PARENT_WRITE_BYTES = 4


def one_pass_bytes(num_edges: int, num_nodes: int) -> int:
    return ((EDGE_READ_BYTES + MASK_WRITE_BYTES) * int(num_edges)
            + PARENT_WRITE_BYTES * int(num_nodes))


def floor_seconds(num_edges: int, num_nodes: int, peak: dict) -> float:
    """Time of one pass at the chip's peak memory bandwidth."""
    return one_pass_bytes(num_edges, num_nodes) / peak["hbm_bytes_per_s"]
