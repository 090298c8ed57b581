"""Engine phases from a profiler trace (bench/phases.py).

A trace recorded on the CPU, of the host round loop solving inside a
``bench.window`` span with the program's annotations on, must give every
device op a phase from the HLO the trace carries, phases that sum to the
busy time ``traces.summarize`` reports, and idle gaps labelled with the
program's host phases, while ``traces.summarize`` itself reads the same
numbers with or without those phase spans.
"""
import tempfile

import numpy as np
import pytest

from bench import phases, traces


@pytest.fixture(scope="module")
def recorded():
    """The trace file of two solves of a 20,000-vertex graph by the host
    round loop (``mst_unoptimized``: one jitted round per dispatch).  The
    CPU does not always trace the ops inside a ``while`` loop, so the
    rounds are dispatched one by one to put every phase at the top."""
    import jax
    from repro.core.mst import mst_unoptimized
    from repro.graphs.generator import generate_graph
    from repro.obs.trace import enable_annotations

    g = generate_graph(20_000, 6, seed=1)
    mst_unoptimized(g)  # compile outside the trace
    enable_annotations(True)
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.solve"):
                    r = mst_unoptimized(g)
                    np.asarray(r.mst_mask)
        jax.profiler.stop_trace()
    finally:
        enable_annotations(False)
    return traces.find_xplane(d)


@pytest.mark.parametrize("op_name,phase", [
    ("jit(_msf_jit)/while/body/mst.scan/gather", "scan"),
    ("jit(f)/mst.hook/vmap(mst.jump)/while/body/gather", "jump"),
    ("jit(batched_msf)/vmap(mst.sort)/sort", "sort"),
    ("jit(f)/mst.finish/mst.jump/while", "jump"),
    ("jit(f)/mst.compact/cumsum", "compact"),
    ("jit(f)/reduce_sum", phases.OTHER),
    ("jit(f)/mst.scanner/add", phases.OTHER),
    ("", phases.OTHER),
])
def test_phase_is_the_innermost_mst_scope(op_name, phase):
    assert phases.phase_of(op_name) == phase


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _instr(name, opcode, op_name=None, called=()):
    out = _bytes(1, name.encode()) + _bytes(2, opcode.encode())
    if op_name is not None:
        out += _bytes(7, _bytes(2, op_name.encode()))
    if called:
        out += _bytes(38, b"".join(_varint(c) for c in called))
    return out


def test_a_fusion_without_metadata_takes_the_phase_fused_into_it():
    """On the TPU the compiler rewrites the segment-min scatter into a
    fusion with no metadata of its own; its operands keep the scope."""
    scatter_body = _int(5, 2) + b"".join(_bytes(2, i) for i in (
        _instr("reshape.1", "reshape", "jit(f)/vmap(mst.scan)/select_n"),
        _instr("transpose.1", "transpose", "jit(f)/vmap(mst.scan)/x"),
        _instr("scatter.1", "scatter")))
    entry = _int(5, 1) + b"".join(_bytes(2, i) for i in (
        _instr("fusion.9", "fusion", called=[2]),
        _instr("fusion.3", "fusion", "jit(f)/mst.hook/gather", called=[2]),
        _instr("while.1", "while", "jit(f)/while", called=[2]),
        _instr("copy.1", "copy")))
    proto = _bytes(1, _bytes(3, entry) + _bytes(3, scatter_body))
    got = phases.hlo_phases(proto)
    assert got == {"fusion.9": "scan", "fusion.3": "hook",
                   "while.1": phases.OTHER, "copy.1": phases.OTHER,
                   "reshape.1": "scan", "transpose.1": "scan",
                   "scatter.1": phases.OTHER}


def test_innermost_op_takes_each_piece_of_busy_time():
    # a while (0-10) holding two ops, one overlapping the next op (9-12),
    # then a gap and a lone op: union 10 + 2 + 3 = 15
    ops = [(0, 10), (1, 4), (5, 9.5), (9, 12), (20, 23)]
    got = phases._innermost_time(ops)
    assert got == {0: 1 + 1 + 0, 1: 3, 2: 4, 3: 3, 4: 3}
    assert sum(got.values()) == 15


def test_every_op_of_the_trace_finds_its_module(recorded):
    ops, _ = phases.read_events(recorded, "cpu")
    with open(recorded, "rb") as f:
        scopes = phases.module_phases(f.read())
    assert ops and all(o.module in scopes for o in ops)
    rounds = {o.module for o in ops
              if o.module.startswith("jit__one_round_jit")}
    assert rounds
    for m in rounds:
        assert {"scan", "hook", "jump"} <= set(scopes[m].values())


def test_phases_sum_to_busy_time(recorded):
    got = phases.summarize_file(recorded, "cpu", 1)
    ops, spans = traces.read_events(recorded, "cpu")
    base = traces.summarize(ops, spans, 1)
    assert set(got.phase_s) == set(phases.PHASES) | {phases.OTHER}
    assert sum(got.phase_s.values()) == pytest.approx(got.busy_s)
    assert got.busy_s == pytest.approx(base.busy_s, rel=1e-3)
    for p in ("scan", "hook", "jump"):
        assert got.phase_s[p] > 0, got.phase_s
    named = got.busy_s - got.phase_s[phases.OTHER]
    assert named >= 0.5 * got.busy_s
    assert got.unmapped_modules == []
    assert all(p in phases.PHASES + (phases.OTHER,)
               for _, p, _ in got.device_ops)


def test_idle_gaps_carry_the_program_host_phases(recorded):
    got = phases.summarize_file(recorded, "cpu", 1)
    labels = dict(got.idle_gaps)
    assert "bench.solve>mst.rank" in labels
    ops, spans = traces.read_events(recorded, "cpu")
    base = traces.summarize(ops, spans, 1)
    assert sum(labels.values()) == pytest.approx(
        sum(dict(base.idle_gaps).values()), rel=1e-6)


def test_trace_summary_is_blind_to_the_phase_spans(recorded):
    """The fields the accepted metrics read come out the same whether or
    not the program marks its host phases in the trace."""
    ops, spans = traces.read_events(recorded, "cpu")
    _, all_spans = phases.read_events(recorded, "cpu")
    assert any(s.name.startswith("mst.") for s in all_spans)
    plain = [s for s in all_spans if not s.name.startswith("mst.")]
    assert sorted(plain) == sorted(spans)
    assert traces.summarize(ops, spans, 1) == traces.summarize(ops, plain, 1)


def test_no_window_no_summary(recorded):
    ops, spans = phases.read_events(recorded, "cpu")
    outside = [s for s in spans if s.name != traces.WINDOW_SPAN]
    assert phases.summarize(ops, outside, {}, 1) is None
