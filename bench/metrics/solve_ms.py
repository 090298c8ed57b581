"""Time per solve: the window over the solves it completed (host clock)."""
from bench import readers


def read(run):
    return readers.ms_per_call(run)
