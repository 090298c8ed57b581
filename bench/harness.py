"""One run of one cell: set-up, warm-up, a timed window, metrics, the check.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic;
* the configuration's ``file`` holds its graph class and the client
  (``bench/clients/<client>.py``) that puts requests to the program;
* the traffic is ``bench/traffic/<traffic>.json``, a data file that
  ``loadgen`` turns into calls, closed loop or open;
* each metric is read by ``bench/metrics/<metric>.py``, whose ``read(run)``
  returns a number, or None where it finds nothing to read.

Set-up makes the graphs from the seed on the host, builds the client and
sends the traffic's first calls, which compile (or load from the
persistent cache) every program the window uses.  The window then sends
calls for ``--seconds``: back to back in a closed loop, on the traffic's
schedule in an open one.  After it closes the peak device
memory is read, the client is closed, and every answer the window
delivered is compared with the plain reference (``reference.py``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

from bench import graphgen, loadgen, phases, reference, traces

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Answer(NamedTuple):
    """What the program returned for one request, on the host."""
    mask: np.ndarray
    parent: np.ndarray
    cached: bool


@dataclass
class Call:
    """One call of the window: when it arrived, started and returned, the
    graphs sent and the host spans.  In a closed loop a call arrives when
    it starts."""
    t_arrive: float
    t0: float
    t1: float
    graphs: List[int]
    spans: Dict[str, float]
    new: List[int] = field(default_factory=list)


class Spans:
    """Host-clock spans of the benchmark around its calls into the program;
    in a traced run each is also a profiler annotation."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.current: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            from jax.profiler import TraceAnnotation
            ctx = TraceAnnotation(name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.current[name] = (self.current.get(name, 0.0)
                              + time.perf_counter() - t0)


class CompileCounter:
    """Backend compiles, persistent-cache loads included, and cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.events = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events += 1
            self.seconds += duration_secs

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


@dataclass
class Run:
    """What the metric readers read."""
    cell: dict
    graphs: list
    calls: List[Call]
    window_s: float
    setup_s: float
    program: Dict[str, float]
    device_kind: str
    trace: Optional[traces.TraceSummary] = None

    @property
    def requests(self) -> int:
        return sum(len(c.graphs) for c in self.calls)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metrics(specs: List[dict], run: Run) -> Dict[str, dict]:
    out = {}
    for m in specs:
        if not metric_applies(m, run.cell["name"]):
            continue
        reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def find_chips(chips: int):
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"needs a TPU, JAX found {jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` in the
    checkout.  Every program is written to it, however fast it compiled.
    The key includes the HLO metadata, so that a cached executable carries
    the ``mst.*`` scopes of the code that runs (``bench/phases.py``), not
    those of the commit that compiled it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def reference_client(graphs, weight_dtype: str) -> Callable:
    """The control: the plain reference at a lower weight precision, put
    in the program's place."""

    def call(indices):
        out = []
        for i in indices:
            g = graphs[i]
            f = reference.minimum_spanning_forest(
                g.src, g.dst, g.weight, g.num_nodes,
                weight_dtype=weight_dtype)
            out.append(Answer(f.mask, f.component, False))
        return out
    return call


def check(graphs, calls: List[Call], answers: List[List[Answer]]) -> dict:
    """Every answer the window delivered against the reference."""
    refs = {}
    wrong_edges = bad_parents = missing = failed = 0
    for c, got in zip(calls, answers):
        missing_here = max(0, len(c.graphs) - len(got))
        missing += missing_here
        failed += missing_here
        for i, a in zip(c.graphs, got):
            if i not in refs:
                g = graphs[i]
                refs[i] = reference.minimum_spanning_forest(
                    g.src, g.dst, g.weight, g.num_nodes)
            wrong, bad = reference.compare(a.mask, a.parent, refs[i])
            wrong_edges += wrong
            bad_parents += bad
            failed += int(wrong > 0 or bad > 0)
    numbers = {"wrong_edges": wrong_edges, "bad_parents": bad_parents,
               "missing": missing}
    return {"numbers": numbers, "failed": failed,
            "limits": {k: 0 for k in numbers}}


def new_graphs(indices: List[int], got: List[Answer]) -> List[int]:
    """The graphs of a call that the program solved rather than recalled:
    not served from its cache, each content once."""
    seen, out = set(), []
    for i, a in zip(indices, got):
        if not a.cached and i not in seen:
            seen.add(i)
            out.append(i)
    return out


def say(*parts) -> None:
    print(*parts, flush=True)


def run_window(call_fn, stream, spans: Spans, seconds: float,
               trace: bool):
    """The window's calls.  In a closed loop they go back to back until
    ``seconds`` have passed, and the last call that started in time is
    waited for and counted.  In an open loop every call that arrives
    within ``seconds`` is sent at its arrival, or when the one before it
    returns if that is later, and the window lasts until the last
    returns."""
    calls: List[Call] = []
    answers: List[List[Answer]] = []
    window_span = contextlib.nullcontext()
    if trace:
        from jax.profiler import TraceAnnotation
        window_span = TraceAnnotation(traces.WINDOW_SPAN)
    with window_span:
        t_window = time.perf_counter()
        deadline = t_window + seconds
        first_at = None
        for plan in stream:
            now = time.perf_counter()
            if plan.at is None:
                if now >= deadline:
                    break
                t_arrive = now
            else:
                first_at = plan.at if first_at is None else first_at
                t_arrive = t_window + plan.at - first_at
                if t_arrive >= deadline:
                    break
                if t_arrive > now:
                    time.sleep(t_arrive - now)
            spans.current = {}
            t0 = time.perf_counter()
            got = call_fn(plan.graphs)
            t1 = time.perf_counter()
            calls.append(Call(t_arrive, t0, t1, plan.graphs,
                              spans.current, new_graphs(plan.graphs, got)))
            answers.append(got)
        window_s = max(time.perf_counter() - t_window, seconds)
    return calls, answers, window_s


def start_trace() -> str:
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def reduce_trace(trace_dir: str, platform: str, chips: int):
    """The window's ``TraceSummary``, with the device time per engine
    phase; prints the phase split on a ``phases:`` line."""
    t = time.perf_counter()
    try:
        xspace = Path(traces.find_xplane(trace_dir)).read_bytes()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ops, host_spans = traces.read_events(xspace, platform)
    summary = traces.summarize(ops, host_spans, chips)
    split = phases.summarize(ops, host_spans, phases.module_phases(xspace),
                             chips)
    if summary is not None:
        summary = summary._replace(phase_s=split.phase_s)
        say("phases: " + json.dumps(split._asdict()))
    say(f"trace: ops={len(ops)} spans={len(host_spans)} "
        f"reduce_s={time.perf_counter() - t!r}")
    return summary


def result_line(run: Run, metrics: dict, verdict: dict, devices,
                peak: int) -> dict:
    numbers, limits = verdict["numbers"], verdict["limits"]
    correct = bool(run.calls) and all(numbers[k] <= limits[k]
                                      for k in numbers)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": run.requests,
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace.device_ops],
            "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in numbers}
    return result


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_chip: bool = True,
             control: Optional[str] = None) -> int:
    """One run; prints the result as the last line of standard output and
    returns the exit code.  ``root`` holds ``BENCHMARK.json``, the
    configuration and traffic files and the compile cache;
    ``require_chip=False`` skips the look for a TPU."""
    bm = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bm["workloads"]}
    if workload not in cells:
        print(f"unknown workload {workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[workload]
    try:
        devices = find_chips(cell["chips"]) if require_chip else None
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    import jax

    devices = (devices or jax.devices())[:cell["chips"]]
    say(f"start: chips_found_s={time.perf_counter() - t_start!r} "
        f"compile cache: {enable_compile_cache(root)}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)
    try:
        result = measure(bm, cell, seed, seconds, trace, t_start=t_start,
                         root=root, devices=devices, compiles=compiles,
                         control=control)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            compiles.on_duration)
        jax.monitoring.unregister_event_listener(compiles.on_event)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def measure(bm: dict, cell: dict, seed: int, seconds: float, trace: bool,
            *, t_start: float, root: Path, devices, compiles: CompileCounter,
            control: Optional[str]) -> dict:
    """Set-up, warm-up, window, metrics and the check of one run."""
    config_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = load_json(root / config_entry["file"])
    traffic = load_json(root / "bench" / "traffic" /
                        f"{cell['traffic']}.json")
    loadgen.validate(traffic)
    seeds = np.random.SeedSequence(int(seed) % 2 ** 64).spawn(2)

    t = time.perf_counter()
    graphs = graphgen.generate_pool(loadgen.graph_classes(traffic, config),
                                    loadgen.pool_size(traffic),
                                    traffic["base_seed"], seeds[0])
    generate_s = time.perf_counter() - t

    spans = Spans(annotate=trace)
    t = time.perf_counter()
    client_mod = load_module(BENCH_DIR / "clients" /
                             f"{config['client']}.py",
                             f"bench_client_{config['client']}")
    client = client_mod.Client(config, graphs, spans, annotate=trace)
    call_fn = (client.call if control is None
               else reference_client(graphs, control))
    build_s = time.perf_counter() - t

    stream = loadgen.calls(traffic, seeds[1])
    t = time.perf_counter()
    for _ in range(loadgen.warm_calls(traffic)):
        call_fn(next(stream).graphs)
    warm_s = time.perf_counter() - t
    setup_compiles = compiles.events
    setup_s = time.perf_counter() - t_start
    say(f"setup: generate_s={generate_s!r} build_s={build_s!r} "
        f"warmup_s={warm_s!r} compiles={compiles.events} "
        f"cache_loads={compiles.cache_hits} "
        f"compile_or_load_s={compiles.seconds!r} setup_s={setup_s!r} "
        f"graphs={len(graphs)}")

    trace_dir = start_trace() if trace else None
    counters0 = client.counters()
    calls, answers, window_s = run_window(call_fn, stream, spans, seconds,
                                          trace)
    counters1 = client.counters()
    if trace:
        import jax
        jax.profiler.stop_trace()
    window_compiles = compiles.events - setup_compiles
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    client.close()
    del client, call_fn
    call_ms = sorted(1e3 * (c.t1 - c.t0) for c in calls) or [0.0]
    say(f"window: calls={len(calls)} requests="
        f"{sum(len(c.graphs) for c in calls)} window_s={window_s!r} "
        f"call_ms_min={call_ms[0]!r} call_ms_median="
        f"{call_ms[len(call_ms) // 2]!r} call_ms_max={call_ms[-1]!r} "
        f"compiles_in_window={window_compiles}")
    if window_compiles:
        print(f"bench: {window_compiles} programs compiled or loaded "
              f"inside the window", file=sys.stderr)

    summary = (reduce_trace(trace_dir, devices[0].platform, cell["chips"])
               if trace else None)
    run = Run(cell=cell, graphs=graphs,
              calls=calls, window_s=window_s, setup_s=setup_s,
              program={k: counters1[k] - counters0.get(k, 0)
                       for k in counters1},
              device_kind=devices[0].device_kind, trace=summary)
    metrics = read_metrics(bm["per_layer"] if trace else bm["end_to_end"],
                           run)

    t = time.perf_counter()
    verdict = check(graphs, calls, answers)
    say(f"reference: compared={run.requests} "
        f"reference_s={time.perf_counter() - t!r}")
    return result_line(run, metrics, verdict, devices, peak)
