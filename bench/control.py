#!/usr/bin/env python3
"""Readings that a cell's output limits are set from: the program's numbers
over many seeds, and each control's (the reference in a lower precision,
``controls`` in the cell's check file) on the same samples, each control
judged by the committed limits as the program is.

    python3 bench/control.py --workload <name> --seeds 1 2 3 --seconds 10

One process, one cell, one seed after another: each seed makes its own
weights, head and traffic, serves a window at the cell's load and reads
every number.  Prints one JSON line per seed and a summary: for each
number the program's largest reading and each control's smallest, and
on how many seeds the program and each control came out correct.
Benchmark runs never run the controls.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    rows = []
    for seed in args.seeds:
        out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                               False, time.perf_counter(), control=True)
        row = {"seed": seed, "correct": out["correct"],
               "control_correct": out["check"]["control_correct"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               **out["check"]["readings"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    controls = list(rows[0]["control_correct"])
    summary = {"workload": args.workload,
               "program_correct": sum(r["correct"] for r in rows),
               "control_correct": {c: sum(r["control_correct"][c]
                                          for r in rows) for c in controls},
               "seeds": len(rows)}
    for mine in rows[0]:
        if any(f"{c}_{mine}" in rows[0] for c in controls):
            summary[mine] = {"program_max": max(r[mine] for r in rows),
                             **{f"{c}_min": min(r[f"{c}_{mine}"]
                                                for r in rows)
                                for c in controls}}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
