"""Device busy time per solve, from the profiler trace."""
from bench import readers


def read(run):
    return readers.device_ms_per_call(run)
