"""Trace reduction, the table of peaks and the one-pass byte count."""
import time
from pathlib import Path

import numpy as np
import pytest

from bench import peaks, traces, work
from bench.traces import Op, Span


def test_union_and_clip():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert traces.clip([(0, 4), (6, 9), (10, 12)], 2, 10) == [(2, 4), (6, 9)]


def test_self_time_leaves_out_nested_ops():
    ops = [Op(0, "while.1", 0, 100), Op(0, "fusion.1", 10, 40),
           Op(0, "fusion.2", 50, 60), Op(0, "fusion.1", 120, 130)]
    got = traces.self_times(ops)
    assert got == {"while.1": 60, "fusion.1": 40, "fusion.2": 10}


def test_op_name():
    assert traces.op_name("%fusion.12 = s32[8]{0} fusion(%a), kind=kLoop") \
        == "fusion.12"
    assert traces.op_name("sort.0") == "sort.0"


def test_summarize_busy_idle_and_labels():
    # Window 0..100 ns; the device runs 10..40, 60..70 (with a nested op)
    # and 95..130.  The first idle gap, 0..10, spans bench.submit (0..5)
    # and bench.flush (5..70); the second, 40..60, lies in
    # bench.flush>mst_solve; the third, 70..95, in no span.
    spans = [Span("bench.window", 0, 100), Span("bench.submit", 0, 5),
             Span("bench.flush", 5, 70), Span("mst_solve:single", 12, 69)]
    ops = [Op(0, "while.1", 10, 40), Op(0, "fusion.3", 15, 25),
           Op(0, "fusion.4", 60, 70), Op(0, "fusion.9", 95, 130)]
    s = traces.summarize(ops, spans, chips=1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)  # 30 + 10 + 5 clipped
    gaps = dict(s.idle_gaps)
    assert gaps == pytest.approx({
        "bench.submit": 5e-9,
        "bench.flush": 5e-9,
        "bench.flush>mst_solve:single": 20e-9,
        traces.IDLE_LABEL: 25e-9})
    assert dict(s.device_ops) == pytest.approx(
        {"while.1": 20e-9, "fusion.3": 10e-9, "fusion.4": 10e-9,
         "fusion.9": 5e-9})


def test_summarize_averages_over_chips():
    spans = [Span("bench.window", 0, 100)]
    ops = [Op(0, "a", 0, 50), Op(1, "a", 0, 100)]
    s = traces.summarize(ops, spans, chips=2)
    assert s.busy_s == pytest.approx(75e-9)


def test_summarize_needs_the_window_span():
    assert traces.summarize([], [Span("bench.solve", 0, 1)], 1) is None


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(200_000, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.solve"):
                    f(x).block_until_ready()
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    xspace = Path(traces.find_xplane(str(tmp_path))).read_bytes()
    ops, spans = traces.read_events(xspace, "cpu")
    assert [s.name for s in spans].count("bench.solve") == 3
    assert any(o.name.startswith("sort") for o in ops)
    s = traces.summarize(ops, spans, chips=1)
    assert 0 < s.busy_s < s.window_s
    assert s.window_s >= 0.06
    labels = dict(s.idle_gaps)
    # The sleeps between calls are idle time with no benchmark span open.
    assert labels[traces.IDLE_LABEL] >= 0.05
    assert s.device_ops[0][1] > 0


def test_peaks_table_is_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_one_pass_bytes_by_hand():
    # Graph1M_6: 3M edges x (4 + 4 + 4 read + 1 written) + 1M x 4 written.
    assert work.one_pass_bytes(3_000_000, 1_000_000) == 43_000_000
    # Graph10K_6, true sizes, not the 32,768 x 16,384 bucket.
    assert work.one_pass_bytes(30_000, 10_000) == 430_000
    assert work.floor_seconds(3_000_000, 1_000_000,
                              peaks.peaks("TPU v5 lite")) == \
        pytest.approx(52.5e-6, rel=1e-3)
