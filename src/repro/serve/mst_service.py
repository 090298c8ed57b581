"""mstserve: micro-batching MST request scheduler + result cache.

The serving analogue of ``serve/decode.py``'s host-side driver, for MST
queries instead of tokens: callers ``submit`` graphs, the service queues
them, and ``flush`` drains the queue in micro-batches —

    queue -> content-hash cache probe -> bucket by padded shape
          -> planned solver per bucket -> scatter responses

Shape bucketing (``graphs/batching.pack_graphs``) keeps the number of
compiled engine variants bounded while mixed request sizes share lanes;
the LRU cache turns repeated graphs (hot queries from millions of users hit
the same road network / social subgraph again and again) into O(1) lookups.

The engine configuration is a validated :class:`repro.core.SolveOptions`
and every solve dispatches through ONE :class:`repro.core.MSTSolver` built
at construction — the hot path never re-derives dispatch, and the solver's
plan-cache counters (``service.solver.stats``) prove warm re-solves of a
seen shape skip retracing.

Everything is synchronous and single-host: the scheduling *structure* is
what later PRs make async / multi-device (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import MSTSolver, SolveOptions, make_solver
from repro.core.solver import legacy_options
from repro.dynamic.delta import MSTDelta
from repro.dynamic.msf import DynamicMSF
from repro.core.types import Graph, GraphLike, as_request, ensure_sized
from repro.graphs.batching import pack_graphs, unpack_results
from repro.obs.exporter import MetricsExporter
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import BATCH_BUCKETS, MetricsRegistry
from repro.obs.span import Span, SpanSampler, now_us, use_span
from repro.obs.trace import collect_phases, pack_time, phase


@dataclass(frozen=True)
class MSTResponse:
    """One solved request, trimmed to the graph's true sizes.

    ``span`` is the request's timing tree (queue-wait / cache-lookup /
    bucket-assembly / solve / scatter, DESIGN.md §4a) when the request
    was sampled, else None.  Cache entries store span-less responses;
    every delivered response gets its own tree (its queue wait differs
    even when the solve was shared).
    """

    request_id: int
    mst_mask: np.ndarray      # (E,) bool
    parent: np.ndarray        # (V,) int32
    total_weight: float
    num_components: int
    num_rounds: int
    cached: bool = False
    span: Optional[Span] = None


@dataclass(frozen=True)
class ClusterResponse:
    """One served clustering request (DESIGN.md §3a).

    ``labels`` are canonical (clusters numbered by first point occurrence),
    so identical point clouds produce bit-identical label arrays across
    engines and cache hits.  ``heights`` exposes the dendrogram merge
    distances for callers that re-cut client-side.
    """

    request_id: int
    labels: np.ndarray        # (n,) int32
    num_clusters: int
    heights: np.ndarray       # (n - c,) float32, nondecreasing
    knn_k: int                # final k that spanned
    escalations: int          # k-doubling rounds taken
    bridges: int              # exact fallback edges appended
    cached: bool = False


def graph_key(graph: Graph, num_nodes: Optional[int] = None) -> str:
    """Content hash of a request — identical graphs dedupe in the cache.

    ``num_nodes`` is only needed for legacy unsized graphs (an unsized
    graph without it gets the curated ``ensure_sized`` error, not an
    opaque hash failure).
    """
    if graph.num_nodes is None or num_nodes is not None:
        graph = ensure_sized(graph, num_nodes)
    h = hashlib.sha1()
    h.update(np.int64(graph.num_nodes).tobytes())
    for arr, dtype in ((graph.src, np.int32), (graph.dst, np.int32),
                      (graph.weight, np.float32)):
        a = np.ascontiguousarray(np.asarray(arr, dtype=dtype))
        h.update(a.tobytes())
    return h.hexdigest()


def points_key(points: np.ndarray, knn_k: int) -> str:
    """Content hash of a clustering request (points + starting k).

    The cached object is the *dendrogram*, which depends on the cloud and
    the escalation start point but not on the cut, so one entry serves
    every ``cut_k`` / ``cut_distance`` the caller asks for.
    """
    a = np.ascontiguousarray(np.asarray(points, np.float32))
    h = hashlib.sha1()
    h.update(np.int64(knn_k).tobytes())
    h.update(np.int64(a.shape[0]).tobytes())
    h.update(a.tobytes())
    return "pts:" + h.hexdigest()


class ServiceStats:
    """Registry-backed service telemetry (DESIGN.md §4).

    The pre-obs surface was a dataclass of bare ints; those attribute
    names survive as *views* over the registry counters, so every
    existing ``svc.stats.cache_hits`` read keeps working while the same
    numbers flow into the Prometheus exposition.  The service mutates
    through the metric handles (``c_*`` counters, ``g_*`` gauges,
    ``h_*`` histograms); outside readers treat the stats as read-only
    (they always did — all writes live inside ``MSTService``).
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = self.registry = (registry if registry is not None
                             else MetricsRegistry("mstserve"))
        self.bucket_shapes: Dict[Tuple[int, int], int] = {}
        self.c_submitted = r.counter("mstserve_requests_total")
        self.c_served = r.counter("mstserve_served_total")
        self.c_cache_hits = r.counter("mstserve_cache_hits_total")
        self.c_engine_solves = r.counter("mstserve_engine_solves_total")
        self.c_flushes = r.counter("mstserve_flushes_total")
        self.c_buckets = r.counter("mstserve_buckets_total")
        self.c_cluster_requests = r.counter(
            "mstserve_cluster_requests_total")
        self.c_cluster_cache_hits = r.counter(
            "mstserve_cluster_cache_hits_total")
        self.c_cluster_escalations = r.counter(
            "mstserve_cluster_escalations_total")
        self.c_update_requests = r.counter("mstserve_update_requests_total")
        self.c_update_inserts = r.counter("mstserve_update_ops_total",
                                          kind="insert")
        self.c_update_deletes = r.counter("mstserve_update_ops_total",
                                          kind="delete")
        self.c_update_tree_added = r.counter(
            "mstserve_update_tree_added_total")
        self.c_update_tree_removed = r.counter(
            "mstserve_update_tree_removed_total")
        self.c_update_resolves = r.counter(
            "mstserve_update_resolves_total")
        self.g_queue_depth = r.gauge("mstserve_queue_depth")
        self.g_hit_rate = r.gauge("mstserve_cache_hit_rate")
        self.h_flush_batch = r.histogram("mstserve_flush_batch_size",
                                         buckets=BATCH_BUCKETS)
        self.h_flush_latency = r.histogram("mstserve_flush_latency_us")
        # Host phases of a request (DESIGN.md §4): content hashing in
        # submit; lane packing plus result trimming per flush (pack), and
        # the trimming alone (trim).
        self.h_hash = r.histogram("mstserve_hash_latency_us")
        self.h_pack = r.histogram("mstserve_pack_latency_us")
        self.h_trim = r.histogram("mstserve_trim_latency_us")
        self.h_update_latency = r.histogram("mstserve_update_latency_us")

    # -- legacy int views ---------------------------------------------------

    @property
    def submitted(self) -> int:
        return int(self.c_submitted.value)

    @property
    def served(self) -> int:
        return int(self.c_served.value)

    @property
    def cache_hits(self) -> int:
        return int(self.c_cache_hits.value)

    @property
    def engine_solves(self) -> int:
        """Lanes actually run through the solver."""
        return int(self.c_engine_solves.value)

    @property
    def flushes(self) -> int:
        return int(self.c_flushes.value)

    @property
    def buckets(self) -> int:
        return int(self.c_buckets.value)

    @property
    def cluster_requests(self) -> int:
        return int(self.c_cluster_requests.value)

    @property
    def cluster_cache_hits(self) -> int:
        return int(self.c_cluster_cache_hits.value)

    @property
    def updates(self) -> int:
        return int(self.c_update_requests.value)

    @property
    def cluster_escalations(self) -> int:
        """k-doubling rounds across cold requests."""
        return int(self.c_cluster_escalations.value)

    @property
    def cache_hit_rate(self) -> float:
        """Lifetime fraction of served requests answered from the LRU."""
        served = self.served
        return self.cache_hits / served if served else 0.0

    def __repr__(self) -> str:
        return (f"ServiceStats(submitted={self.submitted}, "
                f"served={self.served}, cache_hits={self.cache_hits}, "
                f"engine_solves={self.engine_solves}, "
                f"flushes={self.flushes}, buckets={self.buckets})")


class MSTService:
    """Synchronous micro-batching MST server.

    Args:
      options: validated :class:`repro.core.SolveOptions` the service's
        solver is planned from.  ``supports_batched_lanes`` engines (the
        default "batched") solve each flush's cache misses lane-parallel
        through the shape buckets; any other registry engine is dispatched
        per request through the same solver — the queue, dedup, and cache
        layers are identical, so the serving path is a conformance surface
        for every engine.
      variant / engine / compaction: legacy keyword-bag fields, folded into
        a ``SolveOptions`` when ``options`` is not given (deprecation path:
        pass ``options`` in new code).
      max_batch: lane cap per engine call; a bucket with more members
        overflows into multiple solves (bounds padded-batch memory).
      cache_size: LRU capacity in *results*; 0 disables caching.
      sampling: request-span sampling rate in [0, 1] (DESIGN.md §4a).
        1.0 (default) attaches a timing tree to every response and feeds
        the flight recorder; 0.0 turns the span path into a no-op that
        allocates nothing per request (asserted by the obs overhead
        budget test).  Fractional rates sample deterministically (every
        round(1/rate)-th request).
      slow_us: requests whose end-to-end span is at least this many
        microseconds count as "slow" in the flight recorder snapshot
        (None disables the classification).
      export_port: when not None, start a :class:`MetricsExporter`
        thread on this port (0 = ephemeral, see ``svc.exporter.port``)
        serving ``/metrics`` (this service's registry), ``/healthz``,
        ``/readyz`` (solver plan cache warmed) and ``/flight``.  Stop it
        with ``svc.close()`` (or use the service as a context manager).
    """

    def __init__(self, *, options: Optional[SolveOptions] = None,
                 variant: Optional[str] = None,
                 engine: Optional[str] = None,
                 max_batch: Optional[int] = None, cache_size: int = 256,
                 compaction: Optional[int] = None,
                 sampling: float = 1.0,
                 slow_us: Optional[float] = None,
                 export_port: Optional[int] = None):
        if options is None:
            # Legacy keyword bag: keep its documented leniencies (e.g. a
            # compaction cadence on a sequential baseline stays a no-op,
            # and a falsy lane cap means "unbounded").
            options = legacy_options(
                engine or "batched", variant or "cas",
                compaction=compaction or 0,
                max_batch=64 if max_batch is None else max_batch)
        elif any(v is not None for v in (variant, engine, max_batch,
                                         compaction)):
            # Same contract as make_solver: a mixed call would silently
            # drop the caller's explicit keywords.
            raise TypeError("pass either options= or the legacy "
                            "engine/variant/compaction/max_batch keywords, "
                            "not both")
        self.options = options
        # One registry for the whole service: solver metrics (plan hits,
        # solve latency) and service metrics (queue, flush, cache) land
        # in the same place for export.
        self.stats = ServiceStats()
        self.solver: MSTSolver = make_solver(options,
                                             registry=self.stats.registry)
        # Legacy attribute surface (examples/tests read these).
        self.variant = options.variant
        self.engine = options.engine
        self.compaction = options.compaction
        self.max_batch = options.max_batch  # None = unbounded buckets
        self.cache_size = int(cache_size)
        self._cache: "OrderedDict[str, MSTResponse]" = OrderedDict()
        # Guards both LRUs *and* the update() put-new/pop-old pair: the
        # refresh must be atomic so no concurrent solve() ever observes
        # the cache mid-swap (S3 of DESIGN.md §5a).  RLock because
        # _cache_put is also called with the lock already held.
        self._cache_lock = threading.RLock()
        # Dynamic registrations: graph_id -> {"msf": DynamicMSF,
        # "key": current content hash of the canonical graph}.
        self._dynamic: Dict[int, Dict] = {}
        self._next_graph_id = 0
        # Clustering entries (dendrogram + escalation stats) live in their
        # own LRU of the same capacity: one clustering request can imply
        # several graph solves, so the two working sets shouldn't thrash
        # each other.
        self._cluster_cache: "OrderedDict[str, tuple]" = OrderedDict()
        # Request-span plumbing (DESIGN.md §4a): the sampler decides per
        # request at submit time; the flight recorder keeps the last N
        # completed trees + the K slowest for postmortems.
        self.sampler = SpanSampler(sampling)
        self.flight = FlightRecorder(slow_threshold_us=slow_us)
        # pending: (request_id, key, sized_graph, submit_us-or-None);
        # the timestamp doubles as the sampling decision — None means
        # "unsampled", and the unsampled path allocates no span objects.
        self._pending: List[Tuple[int, str, Graph, Optional[float]]] = []
        # solved but not yet handed to any caller (a solve()/solve_many()
        # drained the queue for requests submitted earlier); delivered by
        # the next flush(), in submit order.
        self._unclaimed: List[MSTResponse] = []
        self._next_id = 0
        self.exporter: Optional[MetricsExporter] = None
        if export_port is not None:
            self.exporter = MetricsExporter(
                snapshot_fn=self.stats.registry.to_json,
                # Ready = the solver has compiled at least one plan; a
                # scrape-time exception must read as not-ready, which the
                # exporter handles.
                ready_fn=lambda: self.solver.stats.traces > 0,
                flight=self.flight, port=export_port).start()

    # -- request side -------------------------------------------------------

    def submit(self, graph: GraphLike, num_nodes: Optional[int] = None
               ) -> int:
        """Queue one request (sized graph, or legacy ``graph, num_nodes``);
        returns its request id (flush order = submit order)."""
        g = as_request(graph if num_nodes is None else (graph, num_nodes))
        rid = self._next_id
        self._next_id += 1
        t_sub = now_us() if self.sampler.sample() else None
        t0 = time.perf_counter()
        with phase("hash"):
            key = graph_key(g)
        self.stats.h_hash.observe((time.perf_counter() - t0) * 1e6)
        self._pending.append((rid, key, g, t_sub))
        self.stats.c_submitted.inc()
        self.stats.g_queue_depth.set(len(self._pending))
        return rid

    def flush(self) -> List[MSTResponse]:
        """Drain the queue; responses come back in submit order.

        Also delivers any responses a previous ``solve``/``solve_many``
        computed for earlier submissions but did not claim.
        """
        unclaimed, self._unclaimed = self._unclaimed, []
        pending, self._pending = self._pending, []
        if not pending:
            return unclaimed
        t_flush = time.perf_counter()
        t_flush_us = t_flush * 1e6
        self.stats.c_flushes.inc()
        self.stats.h_flush_batch.observe(len(pending))
        # Span scratch for this flush: shared interval boundaries the
        # sampled requests' trees are built from post-hoc (None when no
        # request in the batch is sampled — the zero-allocation path).
        record: Optional[Dict[str, object]] = (
            {} if any(t is not None for _, _, _, t in pending) else None)

        responses: Dict[int, MSTResponse] = {}
        misses: List[Tuple[int, str, Graph, Optional[float]]] = []
        with phase("cache"):
            for rid, key, g, t_sub in pending:
                hit = self._cache_get(self._cache, key)
                if hit is not None:
                    self.stats.c_cache_hits.inc()
                    responses[rid] = MSTResponse(
                        rid, hit.mst_mask, hit.parent, hit.total_weight,
                        hit.num_components, hit.num_rounds, cached=True)
                else:
                    misses.append((rid, key, g, t_sub))
            if record is not None:
                record["probe_t1"] = now_us()
            # Intra-flush dedup: identical graphs (same content key) share
            # one engine lane; duplicates fan out from the first solve.
            unique: Dict[str, Tuple[int, str, Graph, Optional[float]]] = {}
            for m in misses:
                unique.setdefault(m[1], m)

        if misses:
            solve_list = list(unique.values())
            per_request = self._solve_batch(solve_list, record)
            by_key: Dict[str, MSTResponse] = {}
            for (rid, key, _, _), (mask, parent, tw, nc, nr) in zip(
                    solve_list, per_request):
                # Responses are shared via the cache: freeze the arrays so
                # one caller's mutation can't corrupt later hits.
                mask.setflags(write=False)
                parent.setflags(write=False)
                resp = MSTResponse(rid, mask, parent, tw, nc, nr)
                by_key[key] = resp
                self._cache_put(self._cache, key, resp)
            for rid, key, _, _ in misses:
                base = by_key[key]
                responses[rid] = (base if rid == base.request_id else
                                  MSTResponse(rid, base.mst_mask,
                                              base.parent, base.total_weight,
                                              base.num_components,
                                              base.num_rounds))

        if record is not None:
            miss_rids = {rid for rid, _, _, _ in misses}
            self._attach_spans(pending, responses, miss_rids, record,
                               t_flush_us)
        self.stats.c_served.inc(len(pending))
        self.stats.g_hit_rate.set(self.stats.cache_hit_rate)
        self.stats.h_flush_latency.observe(
            (time.perf_counter() - t_flush) * 1e6)
        # The depth gauge reflects what is queued *now*: requests that
        # arrived during the flush (re-entrant cluster solves) stay
        # visible, and a mid-flush scrape reads the pre-flush depth
        # instead of a premature zero.
        self.stats.g_queue_depth.set(len(self._pending))
        return unclaimed + [responses[rid] for rid, _, _, _ in pending]

    def _attach_spans(self, pending, responses, miss_rids, record,
                      t_flush_us: float) -> None:
        """Build span trees for the flush's sampled requests and attach
        them to the outgoing responses (miss path gets bucket-assembly /
        solve / scatter children; hits get queue-wait + cache-lookup).

        Shared flush intervals (cache probe, lane packing, the bucket
        dispatch a request rode in) appear in every rider's tree as the
        same ``Span`` object, marked ``shared=True`` — per-request
        duplication would only blur that the time *was* shared.
        """
        t_done = now_us()
        solve_by_key = record.get("solve_by_key", {})
        for rid, key, _, t_sub in pending:
            if t_sub is None:
                continue
            resp = responses[rid]
            root = Span("mst_request", t_sub, t_done,
                        attrs={"request_id": rid, "cached": resp.cached,
                               "engine": self.engine,
                               "graph_key": key[:12]})
            root.child("queue_wait", t_sub, t_flush_us)
            root.child("cache_lookup", t_flush_us, record["probe_t1"],
                       shared=True)
            if rid in miss_rids:
                pack = record.get("pack")
                if pack is not None:
                    root.child("bucket_assembly", pack[0], pack[1],
                               shared=True)
                solve = solve_by_key.get(key)
                if solve is not None:
                    root.children.append(solve)
                scatter_t0 = record.get("scatter_t0")
                if scatter_t0 is not None:
                    root.child("scatter", scatter_t0, t_done, shared=True)
            responses[rid] = dataclasses.replace(resp, span=root)
            self.flight.record(root)

    def _solve_batch(self, solve_list, record=None):
        """Solve deduped cache misses through the planned solver.

        Returns per-request ``(mask, parent, tw, nc, nr)`` tuples in
        ``solve_list`` order (the ``unpack_results`` contract).  When
        ``record`` is a dict (some request in the flush is span-sampled)
        the shared interval boundaries land in it: ``pack`` (lane
        packing), ``solve_by_key`` (content key -> the solve span of the
        bucket that request rode in, with the solver's engine dispatch
        attached underneath via ``use_span``), ``scatter_t0``.
        """
        if self.solver.spec.supports_batched_lanes:
            # The collector catches the "pack" and "trim" phases (lane
            # packing, result trimming) running outside the per-bucket
            # dispatches.
            with collect_phases() as phases:
                t0_us = now_us()
                buckets = pack_graphs([g for _, _, g, _ in solve_list],
                                      max_batch=self.max_batch)
                if record is not None:
                    record["pack"] = (t0_us, now_us())
                results = []
                for b in buckets:
                    self.stats.c_buckets.inc()
                    shape = (b.padded_edges, b.padded_nodes)
                    self.stats.bucket_shapes[shape] = (
                        self.stats.bucket_shapes.get(shape, 0)
                        + len(b.indices))
                    self.stats.c_engine_solves.inc(len(b.indices))
                    if record is None:
                        results.append(self.solver.solve_packed(b))
                    else:
                        span = Span("solve", now_us(),
                                    attrs={"shape": f"{shape[0]}x{shape[1]}",
                                           "lanes": len(b.indices),
                                           "shared": len(b.indices) > 1})
                        with use_span(span):
                            results.append(self.solver.solve_packed(b))
                        span.finish()
                        by_key = record.setdefault("solve_by_key", {})
                        for i in b.indices:
                            by_key[solve_list[i][1]] = span
                if record is not None:
                    record["scatter_t0"] = now_us()
                out = unpack_results(buckets, results)
            if pack_time(phases):
                self.stats.h_pack.observe(pack_time(phases) * 1e6)
            if phases.get("trim"):
                self.stats.h_trim.observe(phases["trim"] * 1e6)
            return out
        # Per-graph registry engines: one plan-cached dispatch per request.
        out = []
        for _, key, g, _ in solve_list:
            self.stats.c_engine_solves.inc()
            if record is None:
                r = self.solver.solve(g)
            else:
                span = Span("solve", now_us(),
                            attrs={"shape": f"{g.num_edges}x{g.num_nodes}",
                                   "lanes": 1, "shared": False})
                with use_span(span):
                    r = self.solver.solve(g)
                span.finish()
                record.setdefault("solve_by_key", {})[key] = span
            out.append((np.asarray(r.mst_mask), np.asarray(r.parent),
                        float(r.total_weight), int(r.num_components),
                        int(r.num_rounds)))
        if record is not None:
            record["scatter_t0"] = now_us()
        return out

    def solve(self, graph: GraphLike,
              num_nodes: Optional[int] = None) -> MSTResponse:
        """Convenience: submit one request and flush immediately.

        Requests submitted earlier are solved in the same flush; their
        responses stay queued for the next ``flush()`` call.
        """
        g = as_request(graph if num_nodes is None else (graph, num_nodes))
        return self.solve_many([g])[0]

    def solve_many(self, requests: Sequence[GraphLike]
                   ) -> List[MSTResponse]:
        """Submit a request list and flush once; results in request order.

        Responses for earlier unflushed submissions are retained for the
        next ``flush()`` rather than dropped.
        """
        ids = set(self.submit(r) for r in requests)
        mine: Dict[int, MSTResponse] = {}
        for r in self.flush():
            if r.request_id in ids:
                mine[r.request_id] = r
            else:
                self._unclaimed.append(r)
        return [mine[i] for i in sorted(ids)]

    # -- dynamic graphs (DESIGN.md §5a) -------------------------------------

    def register_dynamic(self, graph: GraphLike, *,
                         resolve_every: int = 0) -> int:
        """Register a mutable graph for streaming updates.

        Solves it once (through this service's solver, so plan caches are
        shared), caches the result under the canonical graph's content
        hash, and returns a ``graph_id`` for :meth:`update`.  The cached
        entry is keyed by the *canonical* edge order (``u <= v``,
        ``(w, u, v)``-lexsorted) — the order ``DynamicMSF`` maintains.

        ``resolve_every`` is the epoch backstop threshold (ops between
        full re-solves; 0 disables).
        """
        dyn = DynamicMSF(as_request(graph), solver=self.solver,
                         resolve_every=resolve_every)
        gid = self._next_graph_id
        self._next_graph_id += 1
        entry: Dict = {"msf": dyn}
        self._refresh_dynamic_entry(entry, dyn)
        self._dynamic[gid] = entry
        return gid

    def dynamic(self, graph_id: int) -> DynamicMSF:
        """The live :class:`DynamicMSF` behind a registered graph id
        (read its ``graph()``/``mask``/``tree_edges()`` views; mutate only
        through :meth:`update` so the cache stays in lockstep)."""
        return self._dynamic[graph_id]["msf"]

    def update(self, graph_id: int, insertions: Sequence = (),
               deletions: Sequence = ()) -> MSTDelta:
        """Apply edge updates to a registered graph; returns the delta.

        Insertions/deletions are ``(u, v, w)`` triples (insertions
        applied first, in order).  The maintained forest stays
        bit-identical to a fresh solve of the mutated graph, and the
        result cache is *refreshed*, not evicted: the entry moves to the
        new structure hash atomically under the cache lock, so a
        concurrent ``solve()`` observes either the old hash -> old MST
        or the new hash -> new MST, never a mix.  Updates to one
        ``graph_id`` must be serialized by the caller; updates to
        different ids and concurrent solves are safe.
        """
        entry = self._dynamic[graph_id]
        dyn: DynamicMSF = entry["msf"]
        t0 = now_us()
        sampled = self.sampler.sample()
        with collect_phases() as acc:
            delta = dyn.apply(insertions, deletions)
            t_apply = now_us()
            self._refresh_dynamic_entry(entry, dyn)
        t1 = now_us()
        st = self.stats
        st.c_update_requests.inc()
        st.c_update_inserts.inc(len(tuple(insertions)))
        st.c_update_deletes.inc(len(tuple(deletions)))
        st.c_update_tree_added.inc(len(delta.added))
        st.c_update_tree_removed.inc(len(delta.removed))
        if delta.resolved:
            st.c_update_resolves.inc()
        st.h_update_latency.observe(t1 - t0)
        if sampled:
            root = Span("mst_update", t0_us=t0, t1_us=t1,
                        attrs={"graph_id": graph_id,
                               "version": delta.version,
                               "churn": delta.churn,
                               "resolved": delta.resolved})
            apply_span = root.child("apply", t0, t_apply)
            for name, secs in acc.items():
                apply_span.attrs[f"{name}_us"] = secs * 1e6
            root.child("cache_refresh", t_apply, t1)
            self.flight.record(root)
        return delta

    def _refresh_dynamic_entry(self, entry: Dict, dyn: DynamicMSF) -> str:
        """Cache the dynamic graph's current MST; drop the stale entry.

        Put-new, pop-old AND the entry's key swing happen under one lock
        hold: a reader holding the lock always finds ``entry["key"]``
        present in the cache, and never observes the swap mid-flight.
        """
        g = dyn.graph()
        resp = MSTResponse(
            request_id=-1,  # cache template; delivered copies get ids
            mst_mask=dyn.mask,
            parent=dyn.forest.uf.roots().astype(np.int32),
            total_weight=dyn.total_weight,
            num_components=dyn.num_components,
            num_rounds=dyn.last_num_rounds,
            cached=False)
        new_key = graph_key(g)
        with self._cache_lock:
            old_key = entry.get("key")
            self._cache_put(self._cache, new_key, resp)
            if old_key is not None and old_key != new_key:
                self._cache.pop(old_key, None)
            entry["key"] = new_key
        return new_key

    # -- clustering ---------------------------------------------------------

    def cluster(self, points, *, num_clusters: Optional[int] = None,
                distance: Optional[float] = None,
                knn_k: Optional[int] = None) -> ClusterResponse:
        """Single-cloud convenience wrapper around ``cluster_many``."""
        return self.cluster_many([points], num_clusters=num_clusters,
                                 distance=distance, knn_k=knn_k)[0]

    def cluster_many(self, clouds: Sequence, *,
                     num_clusters: Optional[int] = None,
                     distance: Optional[float] = None,
                     knn_k: Optional[int] = None) -> List[ClusterResponse]:
        """Serve single-linkage clustering requests end-to-end.

        Pass exactly one of ``num_clusters`` (``cut_k``) / ``distance``
        (``cut_distance``).  Cache-missing clouds run the kNN-EMST pipeline
        (``cluster/emst.py``) with every escalation round's candidate
        graphs routed through ``solve_many`` — i.e. through this service's
        micro-batching queue, shape buckets, intra-flush dedup and graph
        LRU — then the dendrogram is cached under the points' content hash,
        so later requests for the *same cloud with a different cut* are
        pure cache hits.
        """
        from repro.cluster.emst import DEFAULT_K, euclidean_mst_many
        from repro.cluster.linkage import cut_distance, cut_k, single_linkage

        if (num_clusters is None) == (distance is None):
            raise ValueError("pass exactly one of num_clusters / distance")
        if knn_k is None:
            knn_k = DEFAULT_K  # single source for the exactness boundary

        entries: List[Optional[tuple]] = [None] * len(clouds)
        misses: List[Tuple[int, str, np.ndarray]] = []
        for i, pts in enumerate(clouds):
            pts = np.asarray(pts, np.float32)
            self.stats.c_cluster_requests.inc()
            key = points_key(pts, knn_k)
            hit = self._cache_get(self._cluster_cache, key)
            if hit is not None:
                self.stats.c_cluster_cache_hits.inc()
                entries[i] = hit + (True,)
            else:
                misses.append((i, key, pts))

        if misses:
            # Candidate graphs (every escalation round) route through this
            # service's own queue: micro-batching, shape buckets,
            # intra-flush dedup and the graph-level LRU all apply.
            results = euclidean_mst_many([pts for _, _, pts in misses],
                                         k=knn_k,
                                         solve_many_fn=self.solve_many)
            for (i, key, pts), r in zip(misses, results):
                dend = single_linkage(r.src, r.dst, r.distance,
                                      r.num_points)
                dend.heights.setflags(write=False)
                self.stats.c_cluster_escalations.inc(r.escalations)
                entry = (dend, r.knn_k, r.escalations, r.bridges)
                self._cache_put(self._cluster_cache, key, entry)
                entries[i] = entry + (False,)

        out = []
        for rid, entry in enumerate(entries):
            dend, kk, esc, bridges, cached = entry
            labels = (cut_k(dend, num_clusters) if num_clusters is not None
                      else cut_distance(dend, distance))
            labels.setflags(write=False)
            out.append(ClusterResponse(rid, labels,
                                       int(labels.max()) + 1
                                       if labels.size else 0,
                                       dend.heights, kk, esc, bridges,
                                       cached=cached))
        return out

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Stop the exporter thread, if one was started (idempotent)."""
        if self.exporter is not None:
            self.exporter.stop()
            self.exporter = None

    def __enter__(self) -> "MSTService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- caches -------------------------------------------------------------

    def _cache_get(self, cache: OrderedDict, key: str):
        if self.cache_size <= 0:
            return None
        with self._cache_lock:
            resp = cache.get(key)
            if resp is not None:
                cache.move_to_end(key)  # LRU touch
            return resp

    def _cache_put(self, cache: OrderedDict, key: str, resp) -> None:
        if self.cache_size <= 0:
            return
        with self._cache_lock:
            cache[key] = resp
            cache.move_to_end(key)
            while len(cache) > self.cache_size:
                cache.popitem(last=False)

    @property
    def cache_len(self) -> int:
        return len(self._cache)

    @property
    def cluster_cache_len(self) -> int:
        return len(self._cluster_cache)


__all__ = ["MSTService", "MSTResponse", "ClusterResponse", "ServiceStats",
           "graph_key", "points_key"]
