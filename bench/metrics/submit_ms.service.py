"""Benchmark span around a call's MSTService.submit calls, per call."""
from bench import readers


def read(run):
    return readers.span_ms_per_call(run, "bench.submit")
