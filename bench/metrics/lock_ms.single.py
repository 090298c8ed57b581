"""Lock-based hooking's retry waves (``mst.lock``: lock acquire, verify,
parent set, mask commit): device busy time per solve, from the profiler
trace.  A wave's pointer jump counts under ``jump_ms.single``."""
from bench import readers


def read(run):
    return readers.phase_ms_per_call(run, "lock")
