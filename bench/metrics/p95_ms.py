"""95th percentile of request latency over every request of the window."""
from bench import readers


def read(run):
    return readers.latency_p95_ms(run)
