"""Benchmark harness — one section per paper table/figure + framework perf.

  PYTHONPATH=src python -m benchmarks.run [--fast|--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV lines at the end for machine
consumption, with human-readable sections above.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table1,fig2,thm2,sketch_head,engine,"
                         "kernels,roofline")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (slower)")
    ap.add_argument("--backend", default="fused",
                    choices=["fused", "two_kernel", "ref"],
                    help="sketch-head decode backend for the serving "
                         "benchmarks (recorded in the BENCH_*.json head "
                         "metadata; DESIGN.md §8)")
    ap.add_argument("--mesh", default=None,
                    help="'<data>x<model>' serving mesh for the serving "
                         "benchmarks (e.g. 4x2; needs XLA_FLAGS forced "
                         "devices on CPU).  Recorded in every BENCH_*.json "
                         "record's mesh field; default single-device 1x1")
    ap.add_argument("--quant", default=None, choices=["int8", "int4"],
                    help="serve the sketch-head benchmark from quantized "
                         "count-array storage (DESIGN.md §12)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(filter(None, args.only.split(",")))
    csv_rows = []

    def want(name):
        return not only or name in only

    if want("table1"):
        print("== Table 1: accuracy / memory / FLOPs (NN vs Kernel vs RS) ==")
        from benchmarks import table1_repro
        budget = dict(table1_repro.FAST)
        if args.full:
            budget.update(nn_steps=4000, distill_steps=5000, n_points=512,
                          rows=2000, train_cap=10**9, test_cap=10**9)
        t0 = time.time()
        rows = table1_repro.run(budget)
        for r in rows:
            csv_rows.append((f"table1/{r['dataset']}",
                             r["seconds"] * 1e6,
                             f"mem_red={r['mem_reduction']:.1f}x;"
                             f"flop_red={r['flop_reduction']:.1f}x;"
                             f"nn={r['nn']:.3f};rs={r['rs']:.3f}"))
        print(f"  [table1 total {time.time() - t0:.0f}s]\n")

    if want("fig2"):
        print("== Figure 2: accuracy vs memory reduction (vs prune/KD) ==")
        from benchmarks import fig2_tradeoff
        rows = fig2_tradeoff.run("adult")
        for r in rows:
            csv_rows.append((f"fig2/{r['method']}@{r['reduction']:.0f}x",
                             0.0, f"acc={r['acc']:.3f}"))
        print()

    if want("thm2"):
        print("== Theorem 2: MoM error vs bound, swept over L ==")
        from benchmarks import thm2_error
        rows = thm2_error.run()
        for r in rows:
            csv_rows.append((f"thm2/L{r['L']}", 0.0,
                             f"err={r['mean_err']:.4f};"
                             f"cover={r['within_bound']:.3f}"))
        print()

    if want("sketch_head"):
        print("== Sketched LM head vs dense head ==")
        from benchmarks import sketch_head_bench
        r = sketch_head_bench.run(backend=args.backend, mesh=args.mesh,
                                  quant=args.quant)
        csv_rows.append(("sketch_head/dense", r["us_dense"],
                         f"flops={r['dense_flops']}"))
        csv_rows.append((f"sketch_head/{r['head']['backend']}",
                         r["us_sketch"],
                         f"flops={r['sketch_flops']};"
                         f"flop_ratio={r['flop_ratio']:.1f}x;"
                         f"bytes_ratio={r['bytes_ratio']:.2f}x"))
        for mode, e in r["quant_curve"].items():
            csv_rows.append((f"sketch_head/quant_{mode}", 0.0,
                             f"logit_mae={e['logit_mae']:.4f};"
                             f"top1={e['top1_agreement']:.3f};"
                             f"bytes_ratio={e['bytes_ratio']:.2f}x"))
        print()

    if want("engine"):
        print("== Continuous-batching engine vs static batching ==")
        from benchmarks import engine_bench
        r = engine_bench.run(backend=args.backend, mesh=args.mesh)
        csv_rows.append(("engine/static", 0.0,
                         f"tok_s={r['static']['tok_s']:.1f};"
                         f"util={r['static']['slot_utilization']:.2f}"))
        csv_rows.append(("engine/continuous", 0.0,
                         f"tok_s={r['engine']['tok_s']:.1f};"
                         f"util={r['engine']['slot_utilization']:.2f};"
                         f"speedup={r['tok_s_speedup']:.2f}x"))
        for k, m in r["megastep"].items():
            csv_rows.append((f"engine/megastep_k{k}", 0.0,
                             f"tok_s={m['tok_s']:.1f};"
                             f"dispatches={m['megasteps']};"
                             f"host_syncs_per_tok="
                             f"{m['host_syncs_per_token']:.2f}"))
        ht = r["heavy_tail"]
        for mode in ("contiguous", "paged"):
            m = ht[mode]
            csv_rows.append((f"engine/heavy_tail_{mode}", 0.0,
                             f"tok_s={m['tok_s']:.1f};"
                             f"tok_s_slot={m['tokens_per_s_per_slot']:.1f};"
                             f"p50={m['latency_ticks_p50']:.0f};"
                             f"p99={m['latency_ticks_p99']:.0f};"
                             f"prefills={m['prefill_batches']}"))
        csv_rows.append(("engine/heavy_tail_paging", 0.0,
                         f"hit_rate={ht['prefix_hit_rate']:.2f};"
                         f"pages_peak={ht['pages_in_use_peak']};"
                         f"outputs_match={ht['outputs_match']}"))
        print()

    if want("kernels"):
        print("== Kernel micro-benchmarks (cpu reference paths) ==")
        from benchmarks import kernels_bench
        rows = kernels_bench.run()
        for name, us in rows.items():
            csv_rows.append((f"kernels/{name}", us, ""))
        print()

    if want("roofline"):
        print("== Roofline (from dry-run artifacts, if present) ==")
        from benchmarks import roofline
        rows = roofline.run("single", roofline.DRYRUN_TARGET)
        for r in rows:
            csv_rows.append(
                (f"roofline/{r['arch']}/{r['shape']}",
                 r["step_lower_bound_s"] * 1e6,
                 f"bottleneck={r['bottleneck']};"
                 f"roofline={100 * r['roofline_fraction']:.1f}%"))
        print()

    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
