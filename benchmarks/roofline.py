"""§Roofline: three-term analysis per (arch × shape × mesh) from the dry-run.

Reads results/dryrun/*.json (written by repro.launch.dryrun) and computes,
per cell, **per-device seconds** for

    compute    = HLO_dot_FLOPs / peak_FLOPs
    memory     = HLO_bytes_accessed / HBM_bw
    collective = collective_bytes / ICI_bw           (all links of a chip)

with the peaks of one named chip, looked up by ``jax.Device.device_kind``
in :data:`PEAKS` (an unknown kind is an error, never a default).  The
dry-run compiles on CPU placeholder devices standing in for the production
mesh's chips, so its roofline is a projection onto :data:`DRYRUN_TARGET`,
not a measurement of the device that compiled it.

plus MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) and the useful-
compute ratio MODEL/HLO.  The dominant term is the bottleneck the §Perf
loop iterates on.  NOTE: the CPU backend upcasts bf16 arithmetic to f32
before SPMD partitioning, so byte-based terms are ≤2× above their TPU
deployment values for activation traffic (dtype noted in EXPERIMENTS.md).
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.configs import SHAPES, get_config
from repro.models.config import active_param_count, param_count

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}

#: The chip the production meshes of the dry-run stand for (TPU v5e).
DRYRUN_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def model_flops(arch: str, shape: str) -> float:
    cfg = get_config(arch)
    seq, batch, kind = SHAPES[shape]
    n = active_param_count(cfg)
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n * tokens
    # decode: one token per sequence (+ attention reads, excluded from the
    # 2·N model since they're memory- not FLOP-dominated)
    return 2.0 * n * batch


def analyze_cell(path: Path, device_kind: str) -> dict:
    r = json.loads(path.read_text())
    peak = peaks(device_kind)
    chips = r["n_devices"]
    comp = r["flops"] / peak["bf16_flops"]
    # bf16-adjusted bytes when available (CPU backend f32-legalizes bf16
    # before the HLO we parse; raw bytes kept in the JSON for reference).
    mem = r.get("bytes_bf16adj", r["bytes_accessed"]) / peak["hbm_bytes_per_s"]
    coll = r["collective_bytes"]["total"] / peak["ici_bytes_per_s"]
    dom = max(("compute", comp), ("memory", mem), ("collective", coll),
              key=lambda t: t[1])
    mf = model_flops(r["arch"], r["shape"]) / chips
    return {
        **{k: r[k] for k in ("arch", "shape", "mesh", "n_devices")},
        "device_kind": device_kind,
        "compute_s": comp, "memory_s": mem, "collective_s": coll,
        "bottleneck": dom[0], "step_lower_bound_s": dom[1],
        "model_flops_per_chip": mf,
        "useful_ratio": mf / r["flops"] if r["flops"] else 0.0,
        "roofline_fraction": ((mf / peak["bf16_flops"]) / dom[1]
                              if dom[1] else 0.0),
        "temp_bytes": r["memory_analysis"]["temp_size_bytes"],
    }


def run(mesh: str, device_kind: str, write_md: bool = True):
    peaks(device_kind)       # an unknown kind fails before any cell
    rows = []
    for p in sorted(RESULTS.glob(f"*__{mesh}.json")):
        try:
            rows.append(analyze_cell(p, device_kind))
        except Exception as e:  # noqa: BLE001
            print(f"  skip {p.name}: {e!r}")
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print(f"  peaks of {device_kind} ({PEAKS[device_kind]['source']})")
    hdr = (f"  {'arch':22s} {'shape':12s} {'comp_s':>9s} {'mem_s':>9s} "
           f"{'coll_s':>9s} {'bottleneck':>10s} {'useful':>7s} {'roofl%':>7s}")
    print(hdr)
    for r in rows:
        print(f"  {r['arch']:22s} {r['shape']:12s} {r['compute_s']:9.4f} "
              f"{r['memory_s']:9.4f} {r['collective_s']:9.4f} "
              f"{r['bottleneck']:>10s} {r['useful_ratio']:7.2f} "
              f"{100 * r['roofline_fraction']:6.1f}%")
    if write_md and rows:
        out = RESULTS.parent / f"roofline_{mesh}.md"
        lines = ["| arch | shape | compute s | memory s | collective s | "
                 "bottleneck | useful ratio | roofline % |",
                 "|---|---|---|---|---|---|---|---|"]
        for r in rows:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
                f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
                f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
                f"{100 * r['roofline_fraction']:.1f}% |")
        out.write_text("\n".join(lines) + "\n")
        print(f"  wrote {out}")
    return rows
