"""Pointer jumping (``mst.jump``): device busy time per solve, from the
profiler trace."""
from bench import readers


def read(run):
    return readers.phase_ms_per_call(run, "jump")
