"""Result trimming with its device-to-host copy: mstserve_trim_latency_us
per flush."""
from bench import readers


def read(run):
    return readers.program_ms_per(run, "trim_us", "flushes")
