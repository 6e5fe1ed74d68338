#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the program
sustains without a growing backlog.

    python3 bench/sweep.py --workload <open-loop cell> --rates 12 16 20 \
        --seconds 20 --seed 7

One process: weights, head and warm-up once, then for each rate a fresh
engine serves the cell's mix at that rate for ``--seconds``.  Per rate it
prints the requests due and completed per second, the backlog (due but not
admitted) at the middle and at the end of the window, and the tails.  The
cell's traffic file then takes 0.8 of the knee as a fixed number.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import harness, manifest
    from bench.client import Client, percentile
    from bench.traffic import Schedule
    from repro.api.lm import LM

    cell = manifest.find_cell(args.workload, ROOT)
    cfg, mix, ref = cell["config"], cell["traffic"], cell["reference"]
    harness.device_info(jax, cell["cell"]["chips"], True)
    harness.use_compile_cache(jax)
    params, head, _ = harness.build(jax, cfg, ref, args.seed)
    lm = LM(params, harness.program_config(cfg), head)
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        engine = lm.engine(m["slots"], m["max_seq"],
                           decode_chunk=cfg["decode_chunk"])
        sched = Schedule(m, args.seed, cfg["vocab_size"])
        harness.warm_up(engine, sched, cfg["decode_chunk"],
                        cfg["vocab_size"])
        client = Client(engine, sched)
        half = args.seconds / 2
        t0, _ = client.run(0.0)
        client.run(half)
        mid = client.clock()
        backlog_mid = sum(1 for r in client.records.values()
                          if r.due <= mid and r.admit_t is None)
        t0b, t1 = client.run(half)
        recs = list(client.records.values())
        due = [r for r in recs if t0 <= r.due <= t1]
        done = [r for r in recs if r.last_t is not None and r.last_t <= t1]
        backlog_end = sum(1 for r in recs if r.due <= t1 and r.admit_t is None)
        ttft = [((r.first_t if r.first_t is not None else t1) - r.due)
                for r in due]
        tpot = [(r.last_t - r.first_t) / (r.n - 1) for r in done if r.n > 1]
        print(json.dumps({
            "rate": rate, "due_per_s": len(due) / (t1 - t0),
            "done_per_s": len(done) / (t1 - t0),
            "tok_per_s": client.tokens / (t1 - t0),
            "backlog_mid": backlog_mid, "backlog_end": backlog_end,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p95_ms": 1e3 * (percentile(tpot, 95) or np.nan),
            "n": len(due)}), flush=True)
        engine.pool = None
        del engine, client
    return 0


if __name__ == "__main__":
    sys.exit(main())
