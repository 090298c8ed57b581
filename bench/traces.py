"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced run writes one ``.xplane.pb``.  From it this module takes

* the device's operations: on a TPU the events of the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane; on the CPU, which the tests record on,
  the events of the host's XLA threads that carry an ``hlo_op`` stat: the
  client's thread, and the worker threads that run the body of a loop;
  each with the module it ran in, which ``phases.py`` reads the phase of;
* the host spans of the benchmark (``bench.*``) and of the program
  (``mst_solve:<engine>``, and its host phases ``mst.*``), written by
  ``jax.profiler.TraceAnnotation``.

Busy time is the union of the operations' intervals inside the
``bench.window`` span, averaged over the chips the cell uses.  The idle
gaps are the rest of the window; each is split over the host spans open
across it and labelled by them, outermost first, so idle time reads as
what the host was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "mst_solve:", "mst.")
CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
IDLE_LABEL = "no span open"
TOP = 10


class Op(NamedTuple):
    device: int
    name: str
    start_ns: float
    end_ns: float
    module: Optional[str] = None  # "<hlo module>(<program id>)" when known


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class TraceSummary(NamedTuple):
    busy_s: float          # per chip, inside the window
    window_s: float        # length of the bench.window span
    device_ops: List[Tuple[str, float]]   # top ops by self seconds per chip
    idle_gaps: List[Tuple[str, float]]    # idle seconds per host label
    # busy seconds per device phase, per chip (``bench/phases.py``)
    phase_s: Optional[Dict[str, float]] = None


def window(spans: Sequence[Span]) -> Optional[Tuple[float, float]]:
    """Start and end of the one ``bench.window`` span; None without it."""
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        return None
    return windows[0].start_ns, windows[0].end_ns


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def read_events(xspace: bytes, platform: str
                ) -> Tuple[List[Op], List[Span]]:
    """The device operations, each with its module, and the host spans of
    one trace: the bytes of its ``.xplane.pb``."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(xspace)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and platform == "tpu":
            device = int(plane.name.rsplit(":", 1)[1])
            lines = {line.name: line for line in plane.lines}
            modules = [(e.start_ns, e.end_ns, e.name) for e in
                       lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            if "XLA Ops" in lines:
                ops.extend(_tpu_ops(device, lines["XLA Ops"].events,
                                    modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                cpu_ops = platform == "cpu" and line.name.startswith(
                    CPU_OP_LINES)
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append(Span(e.name, e.start_ns, e.end_ns))
                    elif cpu_ops and not e.name.startswith("end:"):
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            ops.append(Op(0, e.name, e.start_ns, e.end_ns,
                                          _module_key(stats)))
    return ops, spans


def _module_key(stats: Dict[str, object]) -> Optional[str]:
    module, program = stats.get("hlo_module"), stats.get("program_id")
    if module is None or program is None:
        return None
    return f"{module}({program})"


def _tpu_ops(device: int, events, modules) -> List[Op]:
    """A TPU op carries no module of its own: it belongs to the ``XLA
    Modules`` event that encloses it in time."""
    out: List[Op] = []
    modules = sorted(modules)
    j = 0
    for e in sorted(events, key=lambda e: e.start_ns):
        key = None
        if modules:
            while j + 1 < len(modules) and modules[j + 1][0] <= e.start_ns:
                j += 1
            s, t, name = modules[j]
            if s <= e.start_ns < t:
                key = name
        out.append(Op(device, op_name(e.name), e.start_ns, e.end_ns, key))
    return out


def op_name(hlo: str) -> str:
    """``fusion.12`` of ``%fusion.12 = s32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Op]) -> Dict[str, float]:
    """Nanoseconds per op name, less the time of the ops nested inside
    it (a ``while`` op spans the ops of its body)."""
    out: Dict[str, float] = {}
    by_device: Dict[int, List[Op]] = {}
    for op in ops:
        by_device.setdefault(op.device, []).append(op)
    for dev_ops in by_device.values():
        stack: List[Op] = []
        for op in sorted(dev_ops, key=lambda o: (o.start_ns, -o.end_ns)):
            while stack and stack[-1].end_ns <= op.start_ns:
                stack.pop()
            d = op.end_ns - op.start_ns
            out[op.name] = out.get(op.name, 0.0) + d
            if stack:
                parent = stack[-1].name
                out[parent] = out.get(parent, 0.0) - d
            stack.append(op)
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted ``[start, end]`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label(open_spans: Sequence[Span]) -> str:
    """The names of the open spans, outermost first."""
    nested = sorted(open_spans, key=lambda s: (s.start_ns, -s.end_ns))
    return ">".join(s.name for s in nested) or IDLE_LABEL


def label_timeline(spans: Sequence[Span], lo: float,
                   hi: float) -> List[Tuple[float, float, str]]:
    """``(start, end, label)`` pieces covering ``[lo, hi]``, in order, over
    which the set of open host spans does not change."""
    points = sorted({lo, hi} | {t for s in spans
                                for t in (s.start_ns, s.end_ns)
                                if lo < t < hi})
    by_start = sorted(spans, key=lambda s: s.start_ns)
    active: List[Span] = []
    j = 0
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        while j < len(by_start) and by_start[j].start_ns <= mid:
            active.append(by_start[j])
            j += 1
        active = [s for s in active if s.end_ns > mid]
        out.append((a, b, label(active)))
    return out


def summarize(ops: Sequence[Op], spans: Sequence[Span],
              chips: int) -> Optional[TraceSummary]:
    """Busy and idle time inside the window; None without a window span."""
    bounds = window(spans)
    if bounds is None:
        return None
    lo, hi = bounds
    inner = [s for s in spans if s.name != WINDOW_SPAN]

    by_device: Dict[int, List[Tuple[float, float]]] = {}
    inside: List[Op] = []
    for op in ops:
        for s, e in clip([(op.start_ns, op.end_ns)], lo, hi):
            by_device.setdefault(op.device, []).append((s, e))
            inside.append(op._replace(start_ns=s, end_ns=e))
    busy_ns = sum(e - s for iv in by_device.values() for s, e in union(iv))

    first = min(by_device) if by_device else None
    merged = union(by_device.get(first, []))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    gaps: Dict[str, float] = {}
    segments = label_timeline(inner, lo, hi)
    i = 0
    for g0, g1 in idle:
        while segments[i][1] <= g0:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g1:
            s0, s1, key = segments[j]
            gaps[key] = gaps.get(key, 0.0) + min(g1, s1) - max(g0, s0)
            j += 1

    def top(d: Dict[str, float]) -> List[Tuple[str, float]]:
        return [(k, v / 1e9 / max(chips, 1)) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return TraceSummary(busy_s=busy_ns / 1e9 / chips,
                        window_s=(hi - lo) / 1e9,
                        device_ops=top(self_times(inside)),
                        idle_gaps=[(k, v / 1e9) for k, v in
                                   sorted(gaps.items(),
                                          key=lambda kv: -kv[1])[:TOP]])
