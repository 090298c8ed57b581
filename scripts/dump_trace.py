"""Generate a Chrome-trace (Perfetto-loadable) timeline from a live demo run.

Usage:
    PYTHONPATH=src python scripts/dump_trace.py [--out trace.json] [--check]

Runs a small frozen MSTService workload with span sampling at 1.0 plus one
``trace_solve`` per-round detail pass, then renders both through
``repro.obs.chrome_trace_doc``:

  * pid 1 — one thread per sampled request, each showing the
    queue_wait / cache_lookup / bucket_assembly / solve / scatter span
    tree (shared flush intervals appear in every request they served);
  * pid 2 — one thread per SolveTrace with rank/pack/solve slices and
    per-round counter tracks (live edges, committed MST edges, hook
    waves, scan bucket).

Load the output at https://ui.perfetto.dev (or chrome://tracing).  With
``--check``, additionally validates the document against the trace-event
schema (``check_chrome_trace``: event fields, slice nesting, non-empty
counters) and exits 1 with one line per problem — the CI trace-schema
step runs exactly this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

DEFAULT_OUT = "trace.json"

# Frozen demo workload: two waves of 4 requests over two bucket shapes,
# one duplicate per wave so the trace shows a shared (aliased) solve span.
SHAPES = ((48, 3), (80, 4))
WAVES = 2


def build_doc() -> dict:
    from repro.core import SolveOptions, make_solver
    from repro.graphs.generator import generate_graph
    from repro.obs import chrome_trace_doc
    from repro.serve.mst_service import MSTService

    svc = MSTService(sampling=1.0)
    # Warm the bucket plans so the trace shows steady-state spans, not
    # one giant compile slice dwarfing everything else.
    for n, deg in SHAPES:
        svc.submit(generate_graph(n, deg, seed=90 + n))
        svc.submit(generate_graph(n, deg, seed=91 + n))
        svc.flush()

    spans = []
    for w in range(WAVES):
        dup = generate_graph(*SHAPES[w % len(SHAPES)], seed=500 + w)
        svc.submit(dup)
        svc.submit(dup)  # same graph twice: result-cache + shared span
        for i, (n, deg) in enumerate(SHAPES):
            svc.submit(generate_graph(n, deg, seed=1000 + w * 10 + i))
        spans += [r.span for r in svc.flush() if r.span is not None]

    # One per-round detail trace for the solve-trace pane: the shared
    # instrumented round loop re-runs the first shape's solve.
    solver = make_solver(SolveOptions(engine="single"))
    _, trace = solver.trace_solve(generate_graph(*SHAPES[0], seed=500))

    return chrome_trace_doc(spans, [trace], label="mst_demo")


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"output path (default: {DEFAULT_OUT})")
    ap.add_argument("--check", action="store_true",
                    help="validate the document against the trace-event "
                         "schema; exit 1 with one line per problem")
    args = ap.parse_args(argv)

    doc = build_doc()
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    n_req = sum(1 for e in doc["traceEvents"]
                if e.get("ph") == "M" and e.get("pid") == 1)
    print(f"wrote {args.out}: {len(doc['traceEvents'])} events, "
          f"{n_req} request thread(s) — load at https://ui.perfetto.dev",
          file=sys.stderr)

    if args.check:
        from repro.obs import check_chrome_trace
        problems = check_chrome_trace(doc)
        for p in problems:
            print(f"PROBLEM: {p}", file=sys.stderr)
        if problems:
            return 1
        print("trace schema ok", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
