"""Content hashing of the submitted graphs: mstserve_hash_latency_us per
flush."""
from bench import readers


def read(run):
    return readers.program_ms_per(run, "hash_us", "flushes")
