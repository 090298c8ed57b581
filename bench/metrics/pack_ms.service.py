"""Lane packing and result trimming: mstserve_pack_latency_us per flush."""
from bench import readers


def read(run):
    return readers.program_ms_per(run, "pack_us", "flushes")
