"""Phase names on the profiler's clock (DESIGN.md §4).

Device side: every engine traces the steps of a Borůvka round under
``jax.named_scope`` (``mst.scan`` ...), which XLA keeps in each compiled
instruction's ``op_name``; these tests lower the engines and pin the names
in the compiled HLO, so a refactor cannot drop them unnoticed.

Host side: ``obs.trace.phase(name)`` is also the profiler annotation
``mst.<name>`` when annotations are on, and costs nothing new when they
are off.  The service's hash / cache / pack / trim phases feed their
histograms, and ``mstserve_pack_latency_us`` keeps meaning packing plus
trimming.
"""
import glob
import re
import tempfile

import jax
import numpy as np
import pytest

from repro.core import SolveOptions, make_solver
from repro.core.batched_mst import batched_msf, pack_padded
from repro.core.engine import rank_edges_host
from repro.core.mst import _msf_jit
from repro.graphs.generator import generate_graph
from repro.obs import trace as obs_trace
from repro.obs.trace import collect_phases, enable_annotations, phase
from repro.serve import mst_service
from repro.serve.mst_service import MSTService

ROUND_SCOPES = ("mst.scan", "mst.hook", "mst.jump", "mst.finish")


@pytest.fixture
def annotations():
    """Turn profiler annotations on for one test, and off again."""
    enable_annotations(True)
    yield
    enable_annotations(False)


def _scopes(hlo_text: str) -> set:
    return set(re.findall(r"\bmst\.[a-z]+\b",
                          " ".join(re.findall(r'op_name="([^"]*)"',
                                              hlo_text))))


def _single_hlo(variant: str, compaction: int) -> str:
    g = generate_graph(200, 4, seed=3)
    rank, order = rank_edges_host(g.weight)
    return _msf_jit.lower(
        g, rank, order, num_nodes=g.num_nodes, variant=variant,
        track_covered=True, max_lock_waves=16, compaction=compaction,
        compaction_kernel=False).compile().as_text()


def _batched_hlo(variant: str, compaction: int) -> str:
    graphs = [generate_graph(100, 4, seed=s) for s in range(2)]
    batch = pack_padded(graphs, padded_edges=256, padded_nodes=128)
    return batched_msf.lower(batch, num_nodes=128, variant=variant,
                             compaction=compaction).compile().as_text()


@pytest.mark.parametrize("engine,variant,compaction,extra", [
    ("single", "cas", 0, ()),
    ("single", "lock", 0, ("mst.lock",)),
    ("single", "cas", 2, ("mst.compact",)),
    ("batched", "cas", 0, ("mst.sort",)),
    ("batched", "lock", 0, ("mst.sort", "mst.lock")),
])
def test_compiled_engine_names_its_device_phases(engine, variant,
                                                 compaction, extra):
    hlo = (_single_hlo if engine == "single" else _batched_hlo)(
        variant, compaction)
    found = _scopes(hlo)
    for name in ROUND_SCOPES + extra:
        assert name in found, (name, sorted(found))
    # Lock waves have a phase of their own; the CAS programs have none.
    if variant == "cas":
        assert "mst.lock" not in found, sorted(found)


def _trace_event_names(fn) -> list:
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    from jax.profiler import ProfileData

    return [e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]


def test_phase_is_a_profiler_annotation_when_on(annotations):
    def body():
        with phase("rank"):
            np.argsort(np.random.default_rng(0).random(10_000))

    names = _trace_event_names(body)
    assert "mst.rank" in names


@pytest.mark.parametrize("collector", [False, True])
def test_phase_off_writes_no_event_and_imports_nothing(monkeypatch,
                                                       collector):
    enable_annotations(False)

    def refuse(*_, **__):
        raise AssertionError("phase() opened a profiler annotation")

    def body():
        with phase("rank"):
            pass

    if collector:
        with collect_phases() as acc:
            names = _trace_event_names(body)
        assert "rank" in acc
    else:
        names = _trace_event_names(body)
    assert not [n for n in names if n.startswith("mst.")]
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with phase("rank"):
        pass


def test_phase_feeds_collector_and_trace_together(annotations):
    with collect_phases() as acc:
        names = _trace_event_names(lambda: rank_edges_host(
            np.random.default_rng(1).random(5_000).astype(np.float32)))
    assert "mst.rank" in names
    assert acc["rank"] > 0


def test_submit_feeds_hash_histogram():
    svc = MSTService(cache_size=0)
    graphs = [generate_graph(80, 3, seed=s) for s in range(3)]
    for g in graphs:
        svc.submit(g)
    h = svc.stats.h_hash
    assert h.count == 3 and h.sum > 0
    svc.flush()
    assert h.count == 3  # flush hashes nothing


def _spy_collectors(monkeypatch, module):
    """Record every phase accumulator ``module`` opens."""
    seen = []
    real = obs_trace.collect_phases

    def spy():
        cm = real()

        class Wrap:
            def __enter__(self):
                acc = cm.__enter__()
                seen.append(acc)
                return acc

            def __exit__(self, *exc):
                return cm.__exit__(*exc)
        return Wrap()

    monkeypatch.setattr(module, "collect_phases", spy)
    return seen


def test_service_pack_counter_is_pack_plus_trim(monkeypatch):
    seen = _spy_collectors(monkeypatch, mst_service)
    svc = MSTService(cache_size=0)
    graphs = [generate_graph(120, 4, seed=s) for s in range(4)]
    svc.solve_many(graphs)
    svc.solve_many(graphs)
    accs = [a for a in seen if "pack" in a]
    assert len(accs) == 2 and all("trim" in a for a in accs)
    pack_us = sum((a["pack"] + a["trim"]) * 1e6 for a in accs)
    trim_us = sum(a["trim"] * 1e6 for a in accs)
    assert svc.stats.h_pack.count == 2
    assert svc.stats.h_pack.sum == pytest.approx(pack_us)
    assert svc.stats.h_trim.count == 2
    assert svc.stats.h_trim.sum == pytest.approx(trim_us)
    assert 0 < svc.stats.h_trim.sum < svc.stats.h_pack.sum


def test_solver_pack_us_is_pack_plus_trim(monkeypatch):
    from repro.core import solver as solver_mod

    seen = _spy_collectors(monkeypatch, solver_mod)
    solver = make_solver(SolveOptions(engine="batched"))
    graphs = [generate_graph(120, 4, seed=s) for s in range(3)]
    solver.solve_many(graphs)
    outer = [a for a in seen if "pack" in a]
    assert len(outer) == 1 and "trim" in outer[0]
    want = (outer[0]["pack"] + outer[0]["trim"]) * 1e6
    assert solver._h_pack.sum == pytest.approx(want)
    assert solver.last_trace.pack_us == pytest.approx(want)


def test_bucket_solve_histogram_is_gone():
    svc = MSTService(cache_size=0)
    svc.solve_many([generate_graph(80, 3, seed=1)])
    names = {m["name"] for m in svc.stats.registry.to_json()["metrics"]}
    assert "mstserve_bucket_solve_latency_us" not in names
    assert {"mstserve_hash_latency_us", "mstserve_pack_latency_us",
            "mstserve_trim_latency_us"} <= names


def test_service_marks_its_host_phases(annotations):
    svc = MSTService(cache_size=0)
    graphs = [generate_graph(80, 3, seed=s) for s in range(2)]
    svc.solve_many(graphs)  # compile outside the trace

    names = set(_trace_event_names(lambda: svc.solve_many(graphs)))
    assert {"mst.hash", "mst.cache", "mst.pack", "mst.trim",
            "mst_solve:batched"} <= names
