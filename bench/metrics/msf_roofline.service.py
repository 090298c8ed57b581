"""One-pass memory floor of the graphs new to the service over device busy time."""
from bench import readers


def read(run):
    return readers.roofline_share(run)
