"""Set-up: process start to the first timed call (host clock)."""
from bench import readers


def read(run):
    return readers.setup_s(run)
