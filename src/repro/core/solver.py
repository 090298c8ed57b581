"""Planned MST solver: configure once, solve many, never re-trace warm.

``make_solver(SolveOptions(...)) -> MSTSolver`` is the public solve surface
(Sanders & Schimek's engineering papers and the serving north-star converge
on the same shape: a solver object configured once, then run over many
graphs).  The solver owns

  * the resolved engine dispatch — registry lookup, variant/capability
    validation, and (for mesh engines) the mesh itself happen ONCE at
    construction, not per call;
  * a **per-shape-bucket plan cache**: each distinct solve shape builds one
    ready-to-call plan closure with every static argument bound, so warm
    re-solves of a seen shape are a dict hit straight into the engine's
    jitted computation (the plan key mirrors the jit cache key — statics
    are fixed per solver, so plan-cache entries and engine traces are
    1:1);
  * hit/trace counters (:class:`SolverStats`) that make "a warm solver
    re-solving an identical shape records 0 new traces" an *assertable*
    property — tests pin it, and the bench harness exports it to
    BENCH_mst.json so retrace regressions trip CI.

``solve_mst`` / ``solve_mst_many`` remain as thin compatibility shims over
a module-level cache of default solvers keyed by options.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.options import MESH_AUTO, SolveOptions
from repro.core.registry import ENGINES
from repro.core.types import Graph, GraphLike, MSTResult, as_request, \
    ensure_sized
from repro.obs.metrics import MetricsRegistry
from repro.obs.span import current_span
from repro.obs.trace import SolveTrace, annotate, collect_phases, pack_time


@dataclasses.dataclass
class SolverStats:
    """Plan-cache telemetry for one :class:`MSTSolver`.

    Attributes:
      solves: graphs solved through this solver (lanes, not engine calls).
      batches: engine invocations (== solves for per-graph engines; one per
        packed shape bucket for lane-parallel engines).
      traces: plan-cache misses — distinct shape buckets this solver has
        compiled a plan for.  A warm solver re-solving a seen shape must
        not grow this.
      plan_hits: plan-cache hits — dispatches served by an existing plan.
      shapes: solve count per plan key.
    """

    solves: int = 0
    batches: int = 0
    traces: int = 0
    plan_hits: int = 0
    shapes: Dict[tuple, int] = dataclasses.field(default_factory=dict)

    @property
    def warm_hit_rate(self) -> float:
        """Fraction of engine dispatches served by an existing plan."""
        total = self.traces + self.plan_hits
        return self.plan_hits / total if total else 0.0


class MSTSolver:
    """A planned solver: one validated configuration, many solves.

    Built by :func:`make_solver`; see the module docstring.  Thread-compat
    with the engines it wraps (everything host-side is plain dict caching).
    """

    def __init__(self, options: SolveOptions,
                 registry: Optional[MetricsRegistry] = None):
        if not isinstance(options, SolveOptions):
            raise TypeError(
                f"make_solver takes a SolveOptions, got "
                f"{type(options).__name__}")
        self.options = options
        self.spec = options.spec
        self.stats = SolverStats()
        self._plans: Dict[tuple, object] = {}
        # Only a concrete Mesh is kept; the 'auto' policy resolves lazily.
        self._mesh = options.mesh if isinstance(options.mesh, Mesh) else None
        # Telemetry (DESIGN.md §4): per-instance registry by default so
        # ``solver.registry`` reads are exact; obs.snapshot() merges all
        # registries for process-wide export.  The label set is fixed per
        # solver, so every metric handle is created once, here.
        self.registry = (registry if registry is not None
                         else MetricsRegistry("mst"))
        lbl = dict(engine=options.engine, variant=options.variant)
        reg = self.registry
        self._m_solves = reg.counter("mst_solves_total", **lbl)
        self._m_batches = reg.counter("mst_batches_total", **lbl)
        self._m_traces = reg.counter("mst_plan_traces_total", **lbl)
        self._m_hits = reg.counter("mst_plan_hits_total", **lbl)
        self._m_rounds = reg.counter("mst_rounds_total", **lbl)
        self._m_waves = reg.counter("mst_waves_total", **lbl)
        self._h_total = reg.histogram("mst_solve_latency_us", **lbl)
        self._h_rank = reg.histogram("mst_rank_latency_us", **lbl)
        self._h_pack = reg.histogram("mst_pack_latency_us", **lbl)
        # Ring of recent SolveTraces (``last_trace`` is traces[-1]).
        self.traces: "deque[SolveTrace]" = deque(maxlen=256)
        self.last_trace: Optional[SolveTrace] = None

    # -- mesh policy --------------------------------------------------------

    @property
    def mesh(self):
        """The mesh this solver runs collectives over (None for
        single-device engines).

        Resolved once: under ``mesh='auto'`` the first access builds a 1-D
        mesh over all local devices and every later solve reuses it — the
        keyword-bag API rebuilt a fresh Mesh on every call.
        """
        if self._mesh is None and self.spec.needs_mesh:
            from repro.core.distributed_mst import make_flat_mesh
            self._mesh = make_flat_mesh()
        return self._mesh

    # -- plan cache ---------------------------------------------------------

    def _plan(self, key: tuple, build):
        """Fetch-or-build the plan for ``key``; returns ``(plan, hit)``."""
        plan = self._plans.get(key)
        hit = plan is not None
        if not hit:
            plan = self._plans[key] = build()
            self.stats.traces += 1
            self._m_traces.inc()
        else:
            self.stats.plan_hits += 1
            self._m_hits.inc()
        self.stats.shapes[key] = self.stats.shapes.get(key, 0) + 1
        return plan, hit

    def _graph_plan(self, graph: Graph):
        """Per-(E, V) plan for the per-graph engines: all statics bound."""
        opts = self.options

        def build():
            solve, mesh = self.spec.solve, self.mesh

            def plan(g: Graph) -> MSTResult:
                return solve(g, variant=opts.variant, mesh=mesh,
                             compaction=opts.compaction,
                             compaction_kernel=opts.compaction_kernel,
                             contraction=opts.contraction)
            return plan

        return self._plan((graph.num_edges, graph.num_nodes), build)

    def _bucket_plan(self, batch_size: int, padded_edges: int,
                     padded_nodes: int):
        """Per-(B, E_pad, V_pad) plan for the lane-parallel engine."""
        opts = self.options

        def build():
            from repro.core.batched_mst import batched_msf

            def plan(batched_graph):
                return batched_msf(batched_graph, num_nodes=padded_nodes,
                                   variant=opts.variant,
                                   compaction=opts.compaction,
                                   contraction=opts.contraction)
            return plan

        return self._plan((batch_size, padded_edges, padded_nodes), build)

    # -- instrumented dispatch ----------------------------------------------

    def _run_plan(self, plan, arg, *, plan_key, plan_hit, batch_size,
                  shape, reader):
        """Run one engine dispatch and emit its :class:`SolveTrace`.

        The dispatch blocks (``jax.block_until_ready``) so the recorded
        latency is honest end-to-end wall time; every caller of a solve
        either blocks immediately after anyway (benchmarks, serving) or
        reads results right away.  Host-side phases deep in the engines
        (``rank_edges_host`` -> "rank", lane packing -> "pack", result
        trimming -> "trim") report into a thread-local collector;
        ``solve_us`` is the remainder.
        ``reader(result)`` pulls ``(rounds, waves, mst_edges)`` — scalar
        device reads, performed after the block.
        """
        with collect_phases() as phases, \
                annotate(f"mst_solve:{self.options.engine}"):
            t0 = time.perf_counter()
            result = plan(arg)
            jax.block_until_ready(result)
            total_us = (time.perf_counter() - t0) * 1e6
        host_phases = {k: v * 1e6 for k, v in phases.items()}
        rank_us = host_phases.get("rank", 0.0)
        pack_us = pack_time(host_phases)
        rounds, waves, mst_edges = reader(result)
        trace = SolveTrace(
            engine=self.options.engine, variant=self.options.variant,
            compaction=self.options.compaction,
            contraction=self.options.contraction, shape=shape,
            batch_size=batch_size, plan_key=plan_key, plan_hit=plan_hit,
            num_rounds=rounds, num_waves=waves, mst_edges=mst_edges,
            rank_us=rank_us, pack_us=pack_us, host_phases=host_phases,
            solve_us=max(0.0, total_us - sum(host_phases.values())),
            total_us=total_us)
        self.traces.append(trace)
        self.last_trace = trace
        # Request-span bridge (DESIGN.md §4a): when the serving layer has
        # a span active on this thread, attach the dispatch as a child so
        # the request's tree carries engine-level detail.  One
        # thread-local read when inactive.
        parent = current_span()
        if parent is not None:
            parent.child(f"engine:{self.options.engine}", t0 * 1e6,
                         t0 * 1e6 + total_us,
                         variant=self.options.variant, plan_hit=plan_hit,
                         rounds=rounds, waves=waves, batch_size=batch_size,
                         rank_us=rank_us, pack_us=pack_us,
                         solve_us=trace.solve_us)
        self._m_solves.inc(batch_size)
        self._m_batches.inc()
        self._m_rounds.inc(rounds)
        self._m_waves.inc(waves)
        self._h_total.observe(total_us)
        if rank_us:
            self._h_rank.observe(rank_us)
        return result

    # -- solving ------------------------------------------------------------

    def solve(self, graph: Graph,
              num_nodes: Optional[int] = None) -> MSTResult:
        """Solve one sized graph (``num_nodes`` only for legacy unsized
        graphs)."""
        graph = ensure_sized(graph, num_nodes)
        if self.spec.supports_batched_lanes:
            return self.solve_many([graph])[0]
        self.stats.solves += 1
        self.stats.batches += 1
        key = (graph.num_edges, graph.num_nodes)
        plan, hit = self._graph_plan(graph)
        num_nodes = graph.num_nodes

        def reader(r):
            return (int(r.num_rounds), int(r.num_waves),
                    num_nodes - int(r.num_components))

        return self._run_plan(plan, graph, plan_key=key, plan_hit=hit,
                              batch_size=1, shape=key, reader=reader)

    def solve_many(self, requests: Sequence[GraphLike]) -> List[MSTResult]:
        """Solve a request list; per-request results in input order.

        Lane-parallel engines shape-bucket the list (pow2 padding,
        ``options.max_batch`` lane cap) and solve each bucket in one engine
        call; every other engine solves per request through its plan cache.
        Lane-packed results are trimmed to each graph's true sizes and are
        therefore *host* (numpy) arrays, already synced — callers timing a
        solve should use ``jax.block_until_ready(result)``, which handles
        both flavours.
        """
        graphs = [as_request(r) for r in requests]
        if not self.spec.supports_batched_lanes:
            return [self.solve(g) for g in graphs]

        from repro.graphs.batching import pack_graphs, unpack_results_mst

        # The outer collector catches the "pack" and "trim" phases (lane
        # packing, result trimming) that run outside the per-bucket
        # dispatches; the per-bucket traces get an even share of that
        # wall time.
        with collect_phases() as outer:
            buckets = pack_graphs(graphs, max_batch=self.options.max_batch)
            results, emitted = [], []
            for b in buckets:
                results.append(self.solve_packed(b))
                emitted.append(self.last_trace)
            out = unpack_results_mst(buckets, results)
        pack_us = pack_time(outer) * 1e6
        if pack_us and emitted:
            self._h_pack.observe(pack_us)
            share = pack_us / len(emitted)
            for t in emitted:
                t.pack_us += share
                t.total_us += share
        return out

    def solve_packed(self, bucket):
        """Solve one pre-packed shape bucket (``graphs.batching
        .PackedBucket``) through the plan cache; returns the padded
        :class:`~repro.core.batched_mst.BatchedMSTResult`.

        The serving layer packs with its own micro-batching policy and
        calls this directly so queue/bucket accounting stays in the
        service while compile caching stays in the solver.
        """
        if not self.spec.supports_batched_lanes:
            raise ValueError(
                f"engine {self.options.engine!r} has no lane-parallel path; "
                f"use solve()/solve_many()")
        self.stats.solves += len(bucket.indices)
        self.stats.batches += 1
        key = (len(bucket.indices), bucket.padded_edges, bucket.padded_nodes)
        plan, hit = self._bucket_plan(*key)
        nn = bucket.graph.num_nodes

        def reader(r):
            return (int(jnp.max(r.num_rounds)), int(jnp.max(r.num_waves)),
                    int(jnp.sum(nn - r.num_components)))

        return self._run_plan(plan, bucket.graph, plan_key=key,
                              plan_hit=hit, batch_size=len(bucket.indices),
                              shape=(bucket.padded_edges,
                                     bucket.padded_nodes), reader=reader)

    def trace_solve(self, graph: Graph, num_nodes: Optional[int] = None):
        """Solve one graph and return ``(result, trace)`` with the
        per-round detail arrays filled in.

        The detail comes from the shared instrumented host round loop
        (:func:`repro.core.mst.round_trace`): the conformance matrix pins
        hooking decisions identical across every engine and compaction
        cadence, so the arrays are engine-exact even though the detail
        pass re-runs the rounds one ``boruvka_round`` at a time.  Use for
        diagnosis, not on hot paths (it re-solves the graph once more).
        """
        from repro.core.engine import scan_bucket_sizes
        from repro.core.mst import round_trace

        graph = ensure_sized(graph, num_nodes)
        result = self.solve(graph)
        trace = self.last_trace
        rt = round_trace(graph, variant=self.options.variant)
        trace.live_per_round = rt.live
        trace.commits_per_round = rt.commits
        trace.waves_per_round = rt.waves
        sizes = scan_bucket_sizes(graph.num_edges)
        trace.buckets_per_round = [
            next(s for s in sizes if s >= c) for c in rt.live]
        return result, trace

    def __repr__(self) -> str:
        return (f"MSTSolver({self.options!r}, traces={self.stats.traces}, "
                f"plan_hits={self.stats.plan_hits})")


def make_solver(options: Optional[SolveOptions] = None, *,
                registry: Optional[MetricsRegistry] = None,
                **kwargs) -> MSTSolver:
    """Build a planned solver.

    Pass a :class:`SolveOptions`, or its fields as keywords::

        solver = make_solver(SolveOptions(engine="batched", variant="lock"))
        solver = make_solver(engine="batched", variant="lock")

    Validation (unknown engine/variant, impossible mesh policy, capability
    mismatches) happens here, eagerly — not at the first solve.
    ``registry`` shares an existing :class:`repro.obs.MetricsRegistry`
    (the serving layer passes its own so service and solver metrics land
    in one place); by default each solver gets a fresh one.
    """
    if options is None:
        options = SolveOptions(**kwargs)
    elif kwargs:
        raise TypeError("pass either a SolveOptions or keyword fields, "
                        "not both")
    return MSTSolver(options, registry=registry)


# ---------------------------------------------------------------------------
# Compatibility shims: the keyword-bag entry points, now thin wrappers over
# a module-level cache of default solvers (one per distinct options value).
# ---------------------------------------------------------------------------

_DEFAULT_SOLVERS: Dict[SolveOptions, MSTSolver] = {}


def default_solver(options: SolveOptions) -> MSTSolver:
    """The shared solver for ``options`` (shims and one-off callers reuse
    plan caches instead of rebuilding dispatch per call)."""
    solver = _DEFAULT_SOLVERS.get(options)
    if solver is None:
        solver = _DEFAULT_SOLVERS[options] = MSTSolver(options)
    return solver


def legacy_options(engine: str, variant: str, mesh=None,
                   compaction: int = 0,
                   max_batch: Optional[int] = None) -> SolveOptions:
    """Fold the legacy keyword bag into a validated ``SolveOptions``.

    Keeps the old surface's documented leniencies so the deprecation path
    (``solve_mst``, ``MSTService(engine=...)``, ``euclidean_mst_many``'s
    engine keywords) cannot change behaviour: a compaction cadence on an
    engine that ignores it is dropped as the no-op it always was, and
    ``mesh=None`` means "build one" (the old default), not "no mesh".
    """
    spec = ENGINES.get(engine)
    if spec is not None and not spec.honors_compaction:
        compaction = 0
    return SolveOptions(engine=engine, variant=variant,
                        compaction=compaction,
                        mesh=mesh if mesh is not None else MESH_AUTO,
                        # Old surface: any falsy cap meant "unbounded".
                        max_batch=max_batch or None)


def solve_mst(graph: Graph, num_nodes: Optional[int] = None, *,
              engine: str = "single", variant: str = "cas", mesh=None,
              compaction: int = 0) -> MSTResult:
    """Dispatch one MST solve through a cached default solver.

    Compatibility shim over ``make_solver(...).solve(...)`` — bit-identical
    results (asserted across the conformance families by
    ``tests/test_api.py``).  New code should build an
    :class:`MSTSolver` and reuse it.
    """
    opts = legacy_options(engine, variant, mesh, compaction)
    return default_solver(opts).solve(graph, num_nodes)


def solve_mst_many(requests: Sequence[GraphLike], *, engine: str = "single",
                   variant: str = "cas", mesh=None,
                   compaction: int = 0) -> List[MSTResult]:
    """Dispatch a list of solves (sized graphs or legacy ``(graph, V)``
    pairs) through a cached default solver; see :meth:`MSTSolver
    .solve_many`."""
    opts = legacy_options(engine, variant, mesh, compaction)
    return default_solver(opts).solve_many(list(requests))


__all__ = ["MSTSolver", "SolverStats", "make_solver", "default_solver",
           "legacy_options", "solve_mst", "solve_mst_many"]
