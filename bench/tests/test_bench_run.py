"""Whole runs of the harness on the CPU at small sizes.

These skip the harness's look for a chip and drive the rest of a run:
set-up, warm-up, window, metrics and the comparison with the reference.
A sound run must come out correct; the control, and the program broken
underneath the timed path in each way a cell can break, must not.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SMALL_NODES = {"paper_1m6_single": 5000, "paper_10k6_service": 300}
SINGLE, SERVICE = "single_1m6_closed", "service_10k6_miss"
OPEN = "service_open_mixed"
# An open loop of Zipf-picked graphs of two classes: a mix that no cell
# uses yet, added to the small root by data alone.
OPEN_TRAFFIC = {"graphs": [{"num_nodes": 200, "avg_degree": 3},
                           {"num_nodes": 300, "avg_degree": 9}],
                "fresh_per_call": 4, "pool": 24, "pick": "zipf",
                "zipf_s": 0.99, "arrival": "poisson", "calls_per_s": 40.0,
                "warm_calls": 3, "base_seed": 5}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root with the benchmark's configurations at small graph sizes and
    its traffic files unchanged."""
    root = tmp_path_factory.mktemp("bench_root")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        n = SMALL_NODES[c["name"]]
        cfg["graph"] = dict(cfg["graph"], num_nodes=n,
                            num_edges=n * cfg["graph"]["avg_degree"] // 2)
        path = root / c["file"]
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(cfg))
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for t in {w["traffic"] for w in bm["workloads"]}:
        name = f"{t}.json"
        (root / "bench" / "traffic" / name).write_text(
            (ROOT / "bench" / "traffic" / name).read_text())
    (root / "bench" / "traffic" / "open_zipf_mixed.json").write_text(
        json.dumps(OPEN_TRAFFIC))
    bm["workloads"].append({"name": OPEN, "config": "paper_10k6_service",
                            "traffic": "open_zipf_mixed", "chips": 1,
                            "why": "an open loop"})
    for m in bm["end_to_end"]:
        if SERVICE in m.get("workloads", []):
            m["workloads"].append(OPEN)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture
def restore_jax_cache():
    """Puts back the compile cache settings and the program's profiler
    annotations, which a run sets for its process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.obs.trace import enable_annotations

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    enable_annotations(False)


def run(root, cell, capsys, *, trace=False, control=None):
    rc = harness.run_cell(cell, 2 ** 31 + 5, 0.3, trace,
                          t_start=time.perf_counter(), root=root,
                          require_chip=False, control=control)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SINGLE, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("cell", [SINGLE, SERVICE])
def test_sound_run_is_correct(small_root, cell, capsys, restore_jax_cache):
    result, err = run(small_root, cell, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"wrong_edges", "bad_parents",
                                     "missing"}
    assert "check wrong_edges: 0 (limit 0)" in err
    metrics = result["metrics"]
    assert metrics["setup_s"]["value"] > 0
    if cell == SINGLE:
        assert set(metrics) == {"setup_s", "solve_ms"}
    else:
        assert set(metrics) == {"setup_s", "graphs_per_s", "p95_ms"}
        assert result["attempted"] % 64 == 0


def test_open_loop_of_mixed_classes_is_correct(small_root, capsys,
                                              restore_jax_cache):
    """Calls arrive on a schedule and wait their turn; a request's latency
    runs from its call's arrival."""
    result, err = run(small_root, OPEN, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 4 == 0
    assert set(result["metrics"]) == {"setup_s", "graphs_per_s", "p95_ms"}
    # At most 0.3 s of arrivals at 40 a second, sent at their arrival.
    assert result["attempted"] <= 4 * 40
    assert result["metrics"]["p95_ms"]["value"] > 0


@pytest.mark.parametrize("cell", [SINGLE, SERVICE])
def test_control_is_not_correct(small_root, cell, capsys,
                                restore_jax_cache):
    result, _ = run(small_root, cell, capsys, control="bfloat16")
    assert result["correct"] is False
    assert result["checks"]["wrong_edges"]["value"] > 0


def flip_first_edge(mask):
    return mask.at[..., 0].set(~mask[..., 0])


def unchanged_state(r):
    """The result of a step that left its state as it was: no edge taken,
    every vertex its own root."""
    parent = np.broadcast_to(np.arange(r.parent.shape[-1], dtype=np.int32),
                             r.parent.shape)
    return r._replace(mst_mask=r.mst_mask & False, parent=parent)


def half_the_lanes(r):
    """The batch's second half left out: its lanes keep their start."""
    half = r.mst_mask.shape[0] // 2
    left = unchanged_state(r)
    return r._replace(
        mst_mask=r.mst_mask.at[half:].set(left.mst_mask[half:]),
        parent=r.parent.at[half:].set(left.parent[half:]))


SINGLE_FAULTS = {
    "answer_altered": lambda r: r._replace(
        mst_mask=flip_first_edge(r.mst_mask)),
    "state_unchanged": unchanged_state,
}
SERVICE_FAULTS = dict(SINGLE_FAULTS, half_batch_left_out=half_the_lanes)


def wrap(fn, fault):
    def broken(*args, **kwargs):
        return fault(fn(*args, **kwargs))
    return broken


@pytest.mark.parametrize("fault", sorted(SINGLE_FAULTS))
def test_single_engine_fault_is_not_correct(small_root, fault, capsys,
                                            monkeypatch, restore_jax_cache):
    import repro.core.mst as mst

    monkeypatch.setattr(mst, "_msf_jit",
                        wrap(mst._msf_jit, SINGLE_FAULTS[fault]))
    result, _ = run(small_root, SINGLE, capsys)
    assert result["correct"] is False
    assert result["failed"] > 0


@pytest.mark.parametrize("fault", sorted(SERVICE_FAULTS))
def test_batched_engine_fault_is_not_correct(small_root, fault, capsys,
                                             monkeypatch, restore_jax_cache):
    import repro.core.batched_mst as batched

    monkeypatch.setattr(batched, "batched_msf",
                        wrap(batched.batched_msf, SERVICE_FAULTS[fault]))
    result, _ = run(small_root, SERVICE, capsys)
    assert result["correct"] is False
    assert result["failed"] > 0


def test_service_that_drops_half_its_responses_is_not_correct(
        small_root, capsys, monkeypatch, restore_jax_cache):
    from repro.serve.mst_service import MSTService

    flush = MSTService.flush

    def half_flush(self):
        out = flush(self)
        return out[:len(out) // 2]

    monkeypatch.setattr(MSTService, "flush", half_flush)
    result, _ = run(small_root, SERVICE, capsys)
    assert result["correct"] is False
    assert result["checks"]["missing"]["value"] == result["attempted"] // 2


def test_traced_run_reports_per_layer_metrics(small_root, capsys,
                                              monkeypatch,
                                              restore_jax_cache):
    # The CPU has no published peaks; give it the v5e's for this run.
    from bench import readers

    monkeypatch.setattr(readers, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    result, _ = run(small_root, SERVICE, capsys, trace=True)
    assert result["correct"] is True
    assert {"submit_ms.service", "pack_ms.service"} <= set(
        result["metrics"])
    device = result["device"]
    assert 0 < device["busy_s"] <= device["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    share = result["metrics"].get("msf_roofline.service")
    assert share is None or 0 < share["value"] <= 100
