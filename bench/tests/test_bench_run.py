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
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]
SINGLE, SERVICE = "single_1m6_closed", "service_10k6_miss"
OPEN = "service_open_mixed"
# A configuration no cell uses yet, added to the small root by data alone:
# the single engine with lock-based hooking, on the single cell's traffic.
LOCK_CONFIG, LOCK = "paper_1m6_lock", "lock_1m6_closed"
# Calls that repeat most of their graphs, so that most answers come from
# the cache: a mix that no cell uses yet, added to the small root by data.
HOT = "service_hot_hits"
HOT_TRAFFIC = {"hot_per_call": 48, "fresh_per_call": 16, "pool": 320,
               "shuffle": True, "warm_calls": 2, "base_seed": 20050613}
# An open loop of Zipf-picked graphs of two classes: a mix that no cell
# uses yet, added to the small root by data alone.
OPEN_TRAFFIC = {"graphs": [{"num_nodes": 200, "avg_degree": 3},
                           {"num_nodes": 300, "avg_degree": 9}],
                "fresh_per_call": 4, "pool": 24, "pick": "zipf",
                "zipf_s": 0.99, "arrival": "poisson", "calls_per_s": 40.0,
                "warm_calls": 3, "base_seed": 5}


def small_nodes(graph: dict) -> int:
    """One rule for every configuration: 1/200 of its graph, between 300
    and 5,000 vertices."""
    return min(5000, max(300, graph["num_nodes"] // 200))


def write_small_config(root: Path, file: str, cfg: dict) -> None:
    n = small_nodes(cfg["graph"])
    cfg = dict(cfg, graph=dict(cfg["graph"], num_nodes=n,
                               num_edges=n * cfg["graph"]["avg_degree"] // 2))
    path = root / file
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root with the benchmark's configurations at small graph sizes and
    its traffic files unchanged, plus an open-loop cell, a cell of cache
    hits and a lock-based configuration that only data adds."""
    root = tmp_path_factory.mktemp("bench_root")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bm["configs"]:
        write_small_config(root, c["file"],
                           json.loads((ROOT / c["file"]).read_text()))
    single = next(c for c in bm["configs"] if c["name"] == "paper_1m6_single")
    lock = json.loads((ROOT / single["file"]).read_text())
    lock = dict(lock, name=LOCK_CONFIG,
                options=dict(lock["options"], variant="lock"))
    lock_file = f"bench/configs/{LOCK_CONFIG}.json"
    write_small_config(root, lock_file, lock)
    bm["configs"].append(dict(single, name=LOCK_CONFIG, file=lock_file))
    bm["workloads"].append({"name": LOCK, "config": LOCK_CONFIG,
                            "traffic": "closed_cycle10", "chips": 1,
                            "why": "lock-based hooking"})
    for m in bm["end_to_end"]:
        if SINGLE in m.get("workloads", []):
            m["workloads"].append(LOCK)
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
    (root / "bench" / "traffic" / "hot_hits.json").write_text(
        json.dumps(HOT_TRAFFIC))
    bm["workloads"].append({"name": HOT, "config": "paper_10k6_service",
                            "traffic": "hot_hits", "chips": 1,
                            "why": "cache hits"})
    for m in bm["end_to_end"]:
        if SERVICE in m.get("workloads", []):
            m["workloads"] += [OPEN, HOT]
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
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    enable_annotations(False)


def run_lines(root, cell, capsys, *, trace=False, control=None):
    """The result, every line of standard output, and standard error."""
    rc = harness.run_cell(cell, 2 ** 31 + 5, 0.3, trace,
                          t_start=time.perf_counter(), root=root,
                          require_chip=False, control=control)
    out = capsys.readouterr()
    assert rc == 0, out.err
    lines = out.out.strip().splitlines()
    return json.loads(lines[-1]), lines, out.err


def run(root, cell, capsys, **kwargs):
    result, _, err = run_lines(root, cell, capsys, **kwargs)
    return result, err


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SINGLE, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def assert_sound(root, cell, result, err):
    """Correct, every request answered, and every end-to-end metric the
    cell's BENCHMARK.json names reported; the calls whole."""
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"wrong_edges", "bad_parents",
                                     "missing"}
    assert "check wrong_edges: 0 (limit 0)" in err
    metrics = result["metrics"]
    assert metrics["setup_s"]["value"] > 0
    bm = json.loads((root / "BENCHMARK.json").read_text())
    assert set(metrics) == {m["name"] for m in bm["end_to_end"]
                            if harness.metric_applies(m, cell)}
    assert all(m["value"] > 0 for m in metrics.values())
    w = next(w for w in bm["workloads"] if w["name"] == cell)
    traffic = json.loads((root / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
    per_call = traffic.get("hot_per_call", 0) + traffic["fresh_per_call"]
    assert result["attempted"] % per_call == 0


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_root, cell, capsys, restore_jax_cache):
    result, err = run(small_root, cell, capsys)
    assert_sound(small_root, cell, result, err)


def test_configuration_added_by_data_alone_is_correct(small_root, capsys,
                                                      restore_jax_cache):
    """A configuration file, a cell and a metric's cell list, and nothing
    else, make a cell the harness runs: here lock-based hooking."""
    cfg = json.loads((small_root / "bench" / "configs" /
                      f"{LOCK_CONFIG}.json").read_text())
    assert cfg["options"]["variant"] == "lock"
    assert cfg["graph"]["num_nodes"] == 5000
    result, err = run(small_root, LOCK, capsys)
    assert_sound(small_root, LOCK, result, err)
    assert set(result["metrics"]) == {"setup_s", "solve_ms"}


def test_configurations_are_sized_by_one_rule(small_root):
    sizes = {c["name"]: json.loads((small_root / c["file"]).read_text())
             ["graph"]["num_nodes"] for c in BM["configs"]}
    assert sizes["paper_1m6_single"] == 5000
    assert sizes["paper_10k6_service"] == 300


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


def test_cache_hit_mix_added_by_data_alone_is_correct(small_root, capsys,
                                                      restore_jax_cache):
    """Three answers in four come from the cache; each is compared."""
    result, err = run(small_root, HOT, capsys)
    assert_sound(small_root, HOT, result, err)
    assert set(result["metrics"]) == {"setup_s", "graphs_per_s", "p95_ms"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(small_root, cell, capsys,
                                restore_jax_cache):
    result, _ = run(small_root, cell, capsys, control="bfloat16")
    assert result["correct"] is False
    assert result["checks"]["wrong_edges"]["value"] > 0


def flip_first_edge(mask):
    if isinstance(mask, np.ndarray):
        mask = mask.copy()
        mask[..., 0] = ~mask[..., 0]
        return mask
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


def alter_cached_answers(monkeypatch):
    """The cache hands back each hit with its first edge flipped."""
    import dataclasses

    from repro.serve.mst_service import MSTService

    get = MSTService._cache_get

    def altered(self, cache, key):
        hit = get(self, cache, key)
        if hit is None:
            return None
        return dataclasses.replace(hit,
                                   mst_mask=flip_first_edge(hit.mst_mask))
    monkeypatch.setattr(MSTService, "_cache_get", altered)


@pytest.mark.parametrize("fault", sorted(SERVICE_FAULTS) +
                         ["cached_answer_altered"])
def test_hot_cell_fault_is_not_correct(small_root, fault, capsys,
                                       monkeypatch, restore_jax_cache):
    """In the cell whose answers come mostly from the cache, a fault in
    the engine or in the answers the cache hands back."""
    if fault == "cached_answer_altered":
        alter_cached_answers(monkeypatch)
    else:
        import repro.core.batched_mst as batched

        monkeypatch.setattr(batched, "batched_msf",
                            wrap(batched.batched_msf,
                                 SERVICE_FAULTS[fault]))
    result, _ = run(small_root, HOT, capsys)
    assert result["correct"] is False
    assert result["failed"] > 0


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


SERVICE_PHASE_METRICS = {"scan_ms.service", "hook_ms.service",
                         "jump_ms.service", "sort_ms.service",
                         "hash_ms.service", "trim_ms.service"}
PHASE_METRICS = {
    SINGLE: {"scan_ms.single", "hook_ms.single", "jump_ms.single"},
    SERVICE: SERVICE_PHASE_METRICS,
}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_the_device_phases(small_root, cell, capsys,
                                            monkeypatch, restore_jax_cache):
    """A ``<phase>_ms.*`` metric is the device time of that engine phase
    per call: the phases a cell reports, and the rest of its ``phases:``
    line, sum to its ``device_ms.*``.  Its program counters are read."""
    from bench import phases, readers

    monkeypatch.setattr(readers, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    result, lines, _ = run_lines(small_root, cell, capsys, trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    split = [json.loads(line[len("phases: "):]) for line in lines
             if line.startswith("phases: ")]
    assert len(split) == 1 and not lines[-1].startswith("phases: ")
    phase_s = split[0]["phase_s"]
    assert set(phases.PHASES) | {phases.OTHER} <= set(phase_s)

    applies = [m for m in BM["per_layer"] if harness.metric_applies(m, cell)]
    named = {m["name"]: m["name"].split("_ms.")[0] for m in applies
             if m["source"] == "device_trace"
             and m["name"].split("_ms.")[0] in phase_s}
    counters = {m["name"] for m in applies
                if m["source"] == "program_counter"}
    assert PHASE_METRICS.get(cell, set()) <= set(named) | counters
    assert set(named) | counters <= set(metrics)
    assert all(metrics[m] > 0 for m in set(named) | counters)

    busy_s = result["device"]["busy_s"]
    assert sum(phase_s.values()) == pytest.approx(busy_s, rel=1e-3)
    device_ms = next(metrics[m["name"]] for m in applies
                     if m["name"].startswith("device_ms."))
    calls = 1e3 * busy_s / device_ms
    rest_ms = 1e3 * sum(v for p, v in phase_s.items()
                        if p not in named.values()) / calls
    assert sum(metrics[m] for m in named) + rest_ms == pytest.approx(
        device_ms, rel=1e-3)
