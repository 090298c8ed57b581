"""Responses delivered in the window, cache hits included, per second."""
from bench import readers


def read(run):
    return readers.answers_per_s(run)
