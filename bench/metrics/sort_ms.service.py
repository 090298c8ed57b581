"""The batched engine's in-jit edge ranking (``mst.sort``): device busy
time per call, from the profiler trace."""
from bench import readers


def read(run):
    return readers.phase_ms_per_call(run, "sort")
