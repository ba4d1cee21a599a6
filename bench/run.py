#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine this process is started on.

    python3 bench/run.py --workload star7-608-1chip --seed 7 --seconds 20 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration, a
traffic mix and its chips.  With ``--trace 0`` the result line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the solves run just before the measured window.
The last line on stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and ``checks``: each number compared
with its limit, also printed as the last lines on stderr).  Without an
accelerator, or with fewer devices than the cell asks for, the run exits
nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace), t_start=T_START, log=log)
    except harness.NoAccelerator as e:
        log(f"bench: {e}")
        return 1
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
