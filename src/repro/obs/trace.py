"""Structured per-solve traces and host-side phase/annotation hooks.

Three small pieces glue the engines to the registry (DESIGN.md §4):

  * :class:`SolveTrace` — the structured record one engine dispatch
    emits: what ran (engine/variant/compaction/shape), how the plan cache
    behaved, how long the rank/solve/pack phases took, and — when filled
    by :meth:`repro.core.MSTSolver.trace_solve` — the per-round detail
    arrays (live edges, cumulative commits, lock waves, compaction scan
    bucket).
  * :func:`phase` / :func:`collect_phases` — a thread-local stack of
    phase accumulators.  Host-side helpers deep inside the engines and
    the service wrap themselves in ``phase(name)``: ``rank``
    (``rank_edges_host``), ``pack`` (``pack_padded``), ``trim``
    (``unpack_results_mst``), ``hash`` and ``cache`` (``MSTService``
    submit and flush).  With annotations on, each phase is also a
    profiler annotation ``mst.<name>``, on the device trace's clock.
    When no collector is active and annotations are off (plain engine
    calls outside the solver) the hook is a no-op costing one attribute
    lookup.
  * :func:`annotate` — opt-in ``jax.profiler.TraceAnnotation`` so
    Perfetto traces show named epochs (``boruvka_round``) and dispatches
    (``mst_solve:<engine>``); off by default, enabled via
    :func:`enable_annotations` or the ``REPRO_OBS_ANNOTATE=1``
    environment variable.

The device side of the same trace is named in the engines:
``core/engine.py`` traces each step of a Borůvka round under a
``jax.named_scope`` (``mst.scan``, ``mst.hook``, ``mst.lock``,
``mst.jump``, ``mst.sort``, ``mst.compact``, ``mst.finish``), which lands
in the compiled HLO's op metadata at no runtime cost.

Phase accounting is *wall time on this thread*: nested collectors do not
double-count because ``phase`` writes into the innermost collector only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

_TLS = threading.local()
PHASE_PREFIX = "mst."  # profiler annotation of phase(name): mst.<name>


def _stack() -> List[Dict[str, float]]:
    s = getattr(_TLS, "stack", None)
    if s is None:
        s = _TLS.stack = []
    return s


@contextlib.contextmanager
def collect_phases() -> Iterator[Dict[str, float]]:
    """Push a phase accumulator; ``phase()`` calls on this thread add
    their seconds to it until the context exits."""
    acc: Dict[str, float] = {}
    stack = _stack()
    stack.append(acc)
    try:
        yield acc
    finally:
        stack.pop()


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Accumulate this block's wall time under ``name`` in the innermost
    active collector.  With annotations on, the block is also the
    profiler annotation ``mst.<name>``, whether or not a collector is
    active.  A no-op when neither applies."""
    stack = _stack()
    if not stack and not _ANNOTATE:
        yield
        return
    acc = stack[-1] if stack else None
    mark = None
    if _ANNOTATE:
        from jax.profiler import TraceAnnotation
        mark = TraceAnnotation(PHASE_PREFIX + name)
        mark.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if acc is not None:
            acc[name] = acc.get(name, 0.0) + (time.perf_counter() - t0)
        if mark is not None:
            mark.__exit__(None, None, None)


def pack_time(phases: Dict[str, float]) -> float:
    """Lane packing plus result trimming, in the unit of ``phases``: what
    every pack counter reports (``SolveTrace.pack_us``,
    ``mst_pack_latency_us``, ``mstserve_pack_latency_us``)."""
    return phases.get("pack", 0.0) + phases.get("trim", 0.0)


# -- profiler annotations ----------------------------------------------------

_ANNOTATE = bool(int(os.environ.get("REPRO_OBS_ANNOTATE", "0") or "0"))


def enable_annotations(on: bool = True) -> None:
    """Toggle ``jax.profiler`` trace annotations process-wide."""
    global _ANNOTATE
    _ANNOTATE = bool(on)


def annotations_enabled() -> bool:
    return _ANNOTATE


def annotate(name: str):
    """A ``jax.profiler.TraceAnnotation(name)`` when annotations are
    enabled, else a no-op context.  Wrap host-side dispatch of named
    epochs (``with annotate("boruvka_round"): ...``) so profiler traces
    carry algorithm-level names instead of bare XLA op soup."""
    if not _ANNOTATE:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


# -- the per-dispatch trace record ------------------------------------------

@dataclasses.dataclass
class SolveTrace:
    """One engine dispatch, as observed from the host.

    Always filled (cheap, no extra device work):

      engine/variant/compaction/contraction: the resolved configuration
        that ran.
      shape: padded ``(num_edges, num_nodes)`` of the dispatch.
      batch_size: lanes in the dispatch (1 for per-graph engines).
      plan_key / plan_hit: plan-cache behaviour of this dispatch.
      num_rounds / num_waves: Borůvka rounds and hook waves (lane max
        for packed dispatches).
      mst_edges: committed forest edges (summed over lanes).
      rank_us / pack_us / solve_us / total_us: wall-time split.  rank is
        host edge ranking, pack is lane packing plus result trimming (the
        ``pack`` and ``trim`` phases; attributed
        evenly across a ``solve_many`` call's buckets), solve is the
        remainder of the blocked dispatch.
      host_phases: every named host phase the dispatch collected, in
        microseconds (superset of rank/pack: the spmm engine adds
        ``ell_build``); ``solve_us`` is total minus their sum.  None on
        traces emitted before the field existed.

    Detail arrays (``None`` unless produced via ``trace_solve``, which
    re-runs the shared instrumented round loop — conformance pins round
    identity across engines, so the arrays are engine-exact):

      live_per_round: live (undecided) edges entering each round.
      commits_per_round: cumulative committed MST edges after each round.
      waves_per_round: cumulative hook waves after each round.
      buckets_per_round: pow2 compaction scan bucket per round.
    """

    engine: str
    variant: str
    compaction: int
    shape: Tuple[int, int]
    batch_size: int
    plan_key: tuple
    plan_hit: bool
    num_rounds: int
    num_waves: int
    mst_edges: int
    rank_us: float
    pack_us: float
    solve_us: float
    total_us: float
    # Contract-Borůvka on/off; defaulted (and therefore declared after the
    # required fields) so existing positional constructions stay valid.
    contraction: bool = False
    host_phases: Optional[Dict[str, float]] = None
    live_per_round: Optional[List[int]] = None
    commits_per_round: Optional[List[int]] = None
    waves_per_round: Optional[List[int]] = None
    buckets_per_round: Optional[List[int]] = None

    @property
    def bucket_transitions(self) -> List[Tuple[int, int]]:
        """Rounds where the compaction scan bucket shrank, as
        ``(round_index, new_bucket)`` pairs (empty without detail)."""
        out: List[Tuple[int, int]] = []
        prev = None
        for i, b in enumerate(self.buckets_per_round or []):
            if b != prev:
                out.append((i, b))
                prev = b
        return out

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["shape"] = list(self.shape)
        d["plan_key"] = list(self.plan_key)
        return d


__all__ = ["SolveTrace", "phase", "collect_phases", "pack_time", "annotate",
           "enable_annotations", "annotations_enabled"]
