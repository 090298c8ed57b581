"""One-pass memory floor of the solved graphs over device busy time."""
from bench import readers


def read(run):
    return readers.roofline_share(run)
