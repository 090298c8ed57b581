"""Device time per engine phase, from the ops that ``traces.read_events``
reads out of the profiler trace.

The engines trace each step of a Borůvka round under a ``jax.named_scope``
(``mst.scan``, ``mst.hook``, ``mst.jump``, ``mst.sort``, ``mst.compact``,
``mst.finish``; any other ``mst.<word>`` scope is a phase of that name
too, so a new step of the program needs no change here).  XLA keeps the
scope in the ``op_name`` of each instruction's metadata, and the trace
file carries the compiled HLO of every module it ran in its
``/host:metadata`` plane.  This module reads that HLO, keyed by module and
instruction name, and so gives every device op its innermost ``mst.*``
scope (``traces.Op.module`` names the module an op ran in): one path on
the TPU and on the CPU the tests record on, whatever numbers XLA gave the
ops in this compile.  A fusion the compiler made without metadata takes
the phase fused into it.  An executable loaded from JAX's persistent
cache keeps the metadata it was compiled with unless
``jax_compilation_cache_include_metadata_in_key`` is on, as the harness
sets it, so that no run reads another commit's scopes.

Busy time is cut into pieces over which the set of running ops does not
change; each piece goes to the phase of the innermost op running (the
one that started last), so the phases, ``other`` included, sum to the
busy time of ``traces.summarize``.  The six phases above are always keys
of ``phase_s``.  The host phases the program marks (``mst.rank``,
``mst.pack``, ``mst.trim``, ``mst.hash``, ``mst.cache``) are among the
spans ``traces`` reads, and label the idle gaps.
"""
from __future__ import annotations

import heapq
import re
from typing import (Dict, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

from bench import traces

PHASES = ("scan", "hook", "jump", "sort", "compact", "finish")
OTHER = "other"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"\bmst\.(\w+)")


class PhaseSummary(NamedTuple):
    phase_s: Dict[str, float]            # busy seconds per phase, per chip
    device_ops: List[Tuple[str, str, float]]  # (op, phase, seconds)
    unmapped_modules: List[str]          # modules with no HLO in the trace


# -- protobuf wire format, enough to walk an XSpace -------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one serialized message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def phase_of(op_name: str) -> str:
    """The innermost ``mst.*`` scope of an HLO ``op_name``, else OTHER."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else OTHER


def _ids(value) -> List[int]:
    """A repeated int64 field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_phases(hlo_proto) -> Dict[str, str]:
    """Instruction name -> phase, over every computation of one
    serialized ``HloProto`` (instruction names are unique in a module).

    A fusion the compiler made without metadata of its own (on the TPU,
    a scatter it rewrites) takes the most common phase of the
    instructions fused into it."""
    comps: Dict[int, List[Tuple[str, str, str, List[int]]]] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:                       # HloProto.hlo_module
            continue
        for f2, comp in _fields(module):
            if f2 != 3:                  # HloModuleProto.computations
                continue
            comp_id, instrs = None, []
            for f3, v3 in _fields(comp):
                if f3 == 5:              # HloComputationProto.id
                    comp_id = v3
                elif f3 == 2:            # HloComputationProto.instructions
                    name, opcode, op_name, called = None, "", "", []
                    for f4, v in _fields(v3):
                        if f4 == 1:      # HloInstructionProto.name
                            name = _text(v)
                        elif f4 == 2:    # HloInstructionProto.opcode
                            opcode = _text(v)
                        elif f4 == 7:    # HloInstructionProto.metadata
                            for f5, v5 in _fields(v):
                                if f5 == 2:  # OpMetadata.op_name
                                    op_name = _text(v5)
                        elif f4 == 38:   # .called_computation_ids
                            called += _ids(v)
                    if name is not None:
                        instrs.append((name, opcode, phase_of(op_name),
                                       called))
            comps[comp_id] = instrs

    resolved: Dict[str, str] = {}

    def resolve(instr, seen) -> str:
        name, opcode, phase, called = instr
        if name in resolved:
            return resolved[name]
        if phase == OTHER and opcode == "fusion":
            counts: Dict[str, int] = {}
            for c in called:
                if c in seen:
                    continue
                for inner in comps.get(c, ()):
                    p = resolve(inner, seen | {c})
                    if p != OTHER:
                        counts[p] = counts.get(p, 0) + 1
            if counts:
                phase = max(counts, key=counts.get)
        resolved[name] = phase
        return phase

    for instrs in comps.values():
        for instr in instrs:
            resolve(instr, frozenset())
    return resolved


def module_phases(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``"<module>(<program id>)"`` -> instruction name -> phase, from the
    HLO the trace's ``/host:metadata`` plane carries."""
    view = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(view):
        if f != 1:                       # XSpace.planes
            continue
        name, stat_names, modules = None, {}, []
        for f2, v in _fields(plane):
            if f2 == 2:                  # XPlane.name
                name = _text(v)
                if name != METADATA_PLANE:
                    break
            elif f2 == 5:                # XPlane.stat_metadata (map entry)
                meta = dict(_fields(dict(_fields(v))[2]))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
            elif f2 == 4:                # XPlane.event_metadata (map entry)
                modules.append(dict(_fields(v))[2])
        if name != METADATA_PLANE:
            continue
        for event in modules:
            module, protos = None, []
            for f3, v in _fields(event):
                if f3 == 2:              # XEventMetadata.name
                    module = _text(v)
                elif f3 == 5:            # XEventMetadata.stats
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1, 0)) == HLO_PROTO_STAT \
                            and 6 in stat:
                        protos.append(stat[6])  # XStat.bytes_value
            for proto in protos:
                out.setdefault(module, {}).update(hlo_phases(proto))
    return out


# -- reduction ---------------------------------------------------------------

def _innermost_time(ops: List[Tuple[float, float]]) -> Dict[int, float]:
    """Nanoseconds per op index, for ``(start, end)`` intervals: every
    piece of the busy time goes to the op running over it that started
    last (shortest on a tie)."""
    points = []
    for i, (s, e) in enumerate(ops):
        if e > s:
            points.append((s, 1, i))
            points.append((e, 0, i))
    points.sort()
    alive, heap = set(), []
    out: Dict[int, float] = {}
    prev = None
    for t, starts, i in points:
        if prev is not None and t > prev:
            while heap and heap[0][2] not in alive:
                heapq.heappop(heap)
            if heap:
                j = heap[0][2]
                out[j] = out.get(j, 0.0) + t - prev
        if starts:
            alive.add(i)
            heapq.heappush(heap, (-ops[i][0], ops[i][1], i))
        else:
            alive.discard(i)
        prev = t
    return out


def summarize(ops: Sequence[traces.Op], spans: Sequence[traces.Span],
              scopes: Dict[str, Dict[str, str]],
              chips: int) -> Optional[PhaseSummary]:
    """Phase seconds inside the ``bench.window`` span, from the ops and
    spans of ``traces.read_events`` and the ``scopes`` of
    ``module_phases``; None without a window."""
    bounds = traces.window(spans)
    if bounds is None:
        return None
    lo, hi = bounds

    def phase(op: traces.Op) -> str:
        return scopes.get(op.module, {}).get(op.name, OTHER)

    phase_ns = {p: 0.0 for p in PHASES + (OTHER,)}
    op_ns: Dict[Tuple[str, str], float] = {}
    by_device: Dict[int, List[traces.Op]] = {}
    for op in ops:
        s, e = max(op.start_ns, lo), min(op.end_ns, hi)
        if e > s:
            by_device.setdefault(op.device, []).append(
                op._replace(start_ns=s, end_ns=e))
    for dev_ops in by_device.values():
        timed = _innermost_time([(o.start_ns, o.end_ns) for o in dev_ops])
        for i, ns in timed.items():
            op = dev_ops[i]
            p = phase(op)
            phase_ns[p] = phase_ns.get(p, 0.0) + ns
            op_ns[(op.name, p)] = op_ns.get((op.name, p), 0.0) + ns
    per_chip = 1e9 * max(chips, 1)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:traces.TOP]
    unmapped = sorted({o.module for o in ops
                       if o.module is not None and o.module not in scopes})
    return PhaseSummary(
        phase_s={p: ns / per_chip for p, ns in phase_ns.items()},
        device_ops=[(name, p, ns / per_chip) for (name, p), ns in top],
        unmapped_modules=unmapped)
