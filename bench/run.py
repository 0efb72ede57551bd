#!/usr/bin/env python3
"""On-chip benchmark: one process runs one cell of BENCHMARK.json once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name, makes the weights on
the device from the seed, warms up the cell's own shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last on stdout: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics from a profiler trace),
``device``, with ``--trace 1`` ``breakdown``, and ``checks`` (each number
compared, with its limit), which is also printed last on stderr.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  Never falls back to the CPU."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib.cell import run_cell
    result, numbers = run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               keep_trace=args.keep_trace)
    for n in numbers:
        print(f"check {n.name} {n.value!r} limit {n.limit!r}"
              f"{'' if n.ok else ' FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
