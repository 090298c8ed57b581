"""Run one cell of the MST benchmark on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last the numbers compared with the reference beside their limits.  A
traced run also prints, before it, a ``phases:`` line: the window's device
time per engine phase (``bench/phases.py``).  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 3.

``--control bfloat16`` puts the plain reference, computed on weights
rounded to bfloat16, in the program's place; such a run has to come out
as not correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    # The benchmark's own modules, then the system under test.
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import run_cell

    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START, control=args.control)


if __name__ == "__main__":
    sys.exit(main())
