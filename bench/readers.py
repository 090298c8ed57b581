"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Each reader takes the ``harness.Run`` of one window and returns a number,
or None where the run has nothing for it to read.  A share of a roofline is
never 0: with no device time there is no share to report.
"""
from __future__ import annotations

import numpy as np

from bench.peaks import peaks
from bench.work import floor_seconds


def setup_s(run):
    return run.setup_s


def ms_per_call(run):
    """Window over calls: one client's time per call, all work included."""
    if not run.calls:
        return None
    return 1e3 * run.window_s / len(run.calls)


def answers_per_s(run):
    if not run.calls:
        return None
    return run.requests / run.window_s


def latency_p95_ms(run):
    """95th percentile over every request of the window, from the raw
    samples: a request waits from its call's arrival (in a closed loop,
    the call's start) to the call's return."""
    lat = [1e3 * (c.t1 - c.t_arrive) for c in run.calls for _ in c.graphs]
    if not lat:
        return None
    return float(np.percentile(lat, 95))


def program_ms_per(run, counter_us: str, per: str):
    """A program's own microsecond counter over the window, per event."""
    n = run.program.get(per)
    if counter_us not in run.program or not n:
        return None
    return run.program[counter_us] / 1e3 / n


def span_ms_per_call(run, name: str):
    spans = [c.spans[name] for c in run.calls if name in c.spans]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(run.calls)


def device_ms_per_call(run):
    if run.trace is None or not run.calls or run.trace.busy_s <= 0:
        return None
    return 1e3 * run.trace.busy_s / len(run.calls)


def phase_ms_per_call(run, phase: str):
    """Device busy time in one engine phase (``bench/phases.py``) per
    call; None where the trace has no time in that phase."""
    if run.trace is None or not run.calls or not run.trace.phase_s:
        return None
    seconds = run.trace.phase_s.get(phase, 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(run.calls)


def idle_share(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def roofline_share(run):
    """One pass over the graphs the program solved in the window, at the
    chip's peak memory bandwidth, over the device's busy time."""
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    peak = peaks(run.device_kind)
    floor = sum(floor_seconds(run.graphs[i].num_edges,
                              run.graphs[i].num_nodes, peak)
                for c in run.calls for i in c.new)
    if floor <= 0:
        return None
    return 100.0 * floor / run.trace.busy_s
