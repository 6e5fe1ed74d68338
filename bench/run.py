#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files it names.  The run needs the chips the cell
asks for: with none (or too few), it exits non-zero and prints no result.
``--trace 1`` reports the per-layer metrics from a profiler trace of the
first seconds of the window; ``--trace 0`` the end-to-end metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace to this directory")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness
    except ImportError as e:
        print(f"[bench] cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 1
    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START,
                               keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
