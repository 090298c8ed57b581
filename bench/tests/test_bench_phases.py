"""Engine phases from a profiler trace (bench/phases.py).

A trace recorded on the CPU, of the host round loop solving inside a
``bench.window`` span with the program's annotations on, must give every
device op a phase from the HLO the trace carries, phases that sum to the
busy time ``traces.summarize`` reports, and idle gaps labelled with the
program's host phases, while ``traces.summarize`` itself reads the same
numbers with or without those phase spans.
"""
import os
import tempfile
from pathlib import Path

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


def read(path):
    """The ops and spans of one trace file, and its phase split."""
    xspace = Path(path).read_bytes()
    ops, spans = traces.read_events(xspace, "cpu")
    return ops, spans, phases.summarize(ops, spans,
                                        phases.module_phases(xspace), 1)


@pytest.mark.parametrize("op_name,phase", [
    ("jit(_msf_jit)/while/body/mst.scan/gather", "scan"),
    ("jit(f)/mst.hook/vmap(mst.jump)/while/body/gather", "jump"),
    ("jit(batched_msf)/vmap(mst.sort)/sort", "sort"),
    ("jit(f)/mst.finish/mst.jump/while", "jump"),
    ("jit(f)/mst.compact/cumsum", "compact"),
    ("jit(f)/reduce_sum", phases.OTHER),
    ("jit(f)/mst.scanner/add", "scanner"),
    ("jit(f)/mst.lock/mst.jump/gather", "jump"),
    ("jit(f)/vmap(mst.lock)/scatter", "lock"),
    ("jit(f)/mst_solve/add", phases.OTHER),
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
    xspace = Path(recorded).read_bytes()
    ops, _ = traces.read_events(xspace, "cpu")
    scopes = phases.module_phases(xspace)
    assert ops and all(o.module in scopes for o in ops)
    rounds = {o.module for o in ops
              if o.module.startswith("jit__one_round_jit")}
    assert rounds
    for m in rounds:
        assert {"scan", "hook", "jump"} <= set(scopes[m].values())


def test_phases_sum_to_busy_time(recorded):
    ops, spans, got = read(recorded)
    base = traces.summarize(ops, spans, 1)
    assert set(got.phase_s) == set(phases.PHASES) | {phases.OTHER}
    busy_s = sum(got.phase_s.values())
    assert busy_s == pytest.approx(base.busy_s, rel=1e-3)
    for p in ("scan", "hook", "jump"):
        assert got.phase_s[p] > 0, got.phase_s
    named = busy_s - got.phase_s[phases.OTHER]
    assert named >= 0.5 * busy_s
    assert got.unmapped_modules == []
    assert all(p in phases.PHASES + (phases.OTHER,)
               for _, p, _ in got.device_ops)


def test_idle_gaps_carry_the_program_host_phases(recorded):
    ops, spans, _ = read(recorded)
    got = traces.summarize(ops, spans, 1)
    labels = dict(got.idle_gaps)
    assert "bench.solve>mst.rank" in labels
    assert sum(labels.values()) == pytest.approx(
        got.window_s - got.busy_s, rel=1e-6)


def test_trace_summary_is_blind_to_the_phase_spans(recorded):
    """The fields the accepted metrics read come out the same whether or
    not the program marks its host phases in the trace; the phases only
    relabel the idle gaps."""
    ops, spans, _ = read(recorded)
    assert any(s.name.startswith("mst.") for s in spans)
    plain = [s for s in spans if not s.name.startswith("mst.")]
    marked, bare = (traces.summarize(ops, spans, 1),
                    traces.summarize(ops, plain, 1))
    assert (marked.busy_s, marked.window_s, marked.device_ops) == \
        (bare.busy_s, bare.window_s, bare.device_ops)
    assert sum(dict(marked.idle_gaps).values()) == pytest.approx(
        sum(dict(bare.idle_gaps).values()), rel=1e-9)
    assert "bench.solve>mst.rank" in dict(marked.idle_gaps)
    assert not any("mst." in k for k, _ in bare.idle_gaps)


def test_reduced_trace_reads_what_it_read_before_the_phases(recorded,
                                                            tmp_path):
    """``harness.reduce_trace`` gives the busy time, window, top ops and
    idle share of the trace reduction without the program's phases, and
    adds the phase split of ``phases.summarize``."""
    import shutil

    from bench import harness

    ops, spans, split = read(recorded)
    before = traces.summarize(
        ops, [s for s in spans if not s.name.startswith("mst.")], 1)
    copy = tmp_path / "trace"
    shutil.copytree(os.path.dirname(recorded), copy)
    after = harness.reduce_trace(str(copy), "cpu", 1)
    assert not copy.exists()
    assert (after.busy_s, after.window_s, after.device_ops) == \
        (before.busy_s, before.window_s, before.device_ops)
    assert 1 - after.busy_s / after.window_s == \
        1 - before.busy_s / before.window_s
    assert after.phase_s == split.phase_s
    assert before.phase_s is None


def test_a_new_scope_is_a_phase_of_its_own(tmp_path):
    """A device scope ``mst.<word>`` that this module does not name reads
    into ``phase_s`` under that word, beside the known phases at 0."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("mst.probe"):
            y = jnp.sort(x) * 2
        return y + 1

    x = jnp.arange(100_000, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(traces.WINDOW_SPAN):
            for _ in range(3):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ops, spans, got = read(traces.find_xplane(str(tmp_path)))
    assert got.phase_s["probe"] > 0
    for p in phases.PHASES:
        assert got.phase_s[p] == 0.0
    assert sum(got.phase_s.values()) == pytest.approx(
        traces.summarize(ops, spans, 1).busy_s)


def test_no_window_no_summary(recorded):
    ops, spans, _ = read(recorded)
    outside = [s for s in spans if s.name != traces.WINDOW_SPAN]
    assert phases.summarize(ops, outside, {}, 1) is None
