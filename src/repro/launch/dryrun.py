import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices let ``make_production_mesh`` build the real 16×16 and 2×16×16
meshes; every step function is ``jax.jit(...).lower(...).compile()``'d
against abstract inputs (no allocation), and the compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (does it fit HBM?)
  * ``cost_analysis()``    — HLO FLOPs / bytes for §Roofline
  * collective bytes       — parsed from the optimized HLO text

Results are dumped as JSON per cell into ``results/dryrun/`` for
benchmarks/roofline.py to consume.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-12b \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both    # everything
"""

import argparse
import functools
import json
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cells, get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (abstract_opt_state, abstract_params,
                                input_specs, opt_config_for, prefill_step,
                                serve_step, train_step)
from repro.optim.adamw import OptimizerConfig
from repro.sharding.ctx import activation_sharding
from repro.sharding.rules import (batch_spec, cache_shardings,
                                  params_shardings, zero1_shardings)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

def build_cell(arch: str, shape: str, mesh, *, smoke: bool = False):
    """Return (jitted_fn, example_args (abstract), donate info) for a cell."""
    spec = input_specs(arch, shape, smoke=smoke)
    cfg = spec["cfg"]
    kind = spec["kind"]
    params = abstract_params(cfg)
    pshard = params_shardings(params, mesh)
    bspec = batch_spec(spec["batch"], mesh)
    repl = NamedSharding(mesh, P())

    if kind == "train":
        opt_cfg = opt_config_for(cfg)
        opt = abstract_opt_state(cfg, lean=opt_cfg.lean)
        oshard = type(opt)(
            step=repl,
            mu=zero1_shardings(opt.mu, mesh),
            nu=zero1_shardings(opt.nu, mesh),
            master=(None if opt.master is None
                    else zero1_shardings(opt.master, mesh)),
        )
        bshard = {k: NamedSharding(mesh, P(bspec, *([None] * (v.ndim - 1))))
                  for k, v in spec["batch_inputs"].items()}
        metrics_shard = {k: repl for k in
                         ("loss", "ce", "aux", "grad_norm", "lr")}
        fn = functools.partial(train_step, cfg=cfg, opt_cfg=opt_cfg)
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, metrics_shard),
            donate_argnums=(0, 1),
        )
        args = (params, opt, spec["batch_inputs"])
    elif kind == "prefill":
        tshard = NamedSharding(mesh, P(bspec, None))
        eshard = (NamedSharding(mesh, P(bspec, None, None))
                  if spec["encoder_states"] is not None else None)
        logit_shard = NamedSharding(mesh, P(bspec, "model"))
        if spec["encoder_states"] is not None:
            def fn(p, t, e, _cfg=cfg):
                return prefill_step(p, t, _cfg, encoder_states=e)
            jitted = jax.jit(fn, in_shardings=(pshard, tshard, eshard),
                             out_shardings=logit_shard)
            args = (params, spec["tokens"], spec["encoder_states"])
        else:
            fn = functools.partial(prefill_step, cfg=cfg)
            jitted = jax.jit(fn, in_shardings=(pshard, tshard),
                             out_shardings=logit_shard)
            args = (params, spec["tokens"])
    else:  # decode
        cache = spec["cache"]
        cshard = cache_shardings(cache, mesh, spec["batch"])
        tshard = NamedSharding(mesh, P(bspec, None))
        logit_shard = NamedSharding(mesh, P(bspec, "model"))
        if spec["encoder_states"] is not None:
            eshard = NamedSharding(mesh, P(bspec, None, None))
            def fn(p, c, t, pos, e, _cfg=cfg):
                return serve_step(p, c, t, pos, _cfg, encoder_states=e)
            jitted = jax.jit(fn,
                             in_shardings=(pshard, cshard, tshard, repl, eshard),
                             out_shardings=(logit_shard, cshard),
                             donate_argnums=(1,))
            args = (params, cache, spec["tokens"], spec["pos"],
                    spec["encoder_states"])
        else:
            fn = functools.partial(serve_step, cfg=cfg)
            jitted = jax.jit(fn,
                             in_shardings=(pshard, cshard, tshard, repl),
                             out_shardings=(logit_shard, cshard),
                             donate_argnums=(1,))
            args = (params, cache, spec["tokens"], spec["pos"])
    return jitted, args, cfg


def run_cell(arch: str, shape: str, mesh_kind: str, *, smoke: bool = False,
             save: bool = True, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with mesh, activation_sharding(mesh):
        jitted, args, cfg = build_cell(arch, shape, mesh, smoke=smoke)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    hl = analyze(hlo)

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "n_devices": int(np.prod(mesh.devices.shape)),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # per-device numbers from the trip-weighted HLO analyzer
        "flops": hl["flops"],
        "elementwise_flops": hl["elementwise_flops"],
        "bytes_accessed": hl["bytes_accessed"],
        "bytes_bf16adj": hl["bytes_bf16adj"],
        "collective_bytes": hl["collective_bytes"],
        # raw cost_analysis for reference (undercounts scan bodies)
        "xla_flops": float(cost.get("flops", -1.0)) if cost else -1.0,
        "xla_bytes": float(cost.get("bytes accessed", -1.0)) if cost else -1.0,
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "n_periods": cfg.n_periods,
    }
    if verbose:
        print(f"[{arch} × {shape} × {mesh_kind}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s  "
              f"flops={result['flops']:.3e} "
              f"coll={hl['collective_bytes']['total']:.3e}B "
              f"temp={result['memory_analysis']['temp_size_bytes']}")
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        out = RESULTS_DIR / f"{arch}__{shape}__{mesh_kind}.json"
        out.write_text(json.dumps(result, indent=1))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable (arch × shape) cell")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (CI sanity)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = (list(cells()) if args.all
            else [(args.arch, args.shape)])
    failures = []
    for arch, shape in todo:
        for mk in meshes:
            try:
                run_cell(arch, shape, mk, smoke=args.smoke)
            except Exception as e:  # noqa: BLE001 — report-and-continue CLI
                failures.append((arch, shape, mk, repr(e)[:200]))
                print(f"FAIL [{arch} × {shape} × {mk}]: {e!r}",
                      file=sys.stderr)
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nAll dry-run cells compiled.")


if __name__ == "__main__":
    main()
