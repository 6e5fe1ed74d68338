"""The fused sketch-head kernel's share of its roofline: the least time the
chip needs for the work the calls require (count array read once per call,
hiddens in, f32 logits out; ``bench/costs.py``), over the kernel's time in
the trace.  The bound that applies is the larger of operations over the
bf16 peak and bytes over the HBM bandwidth: bytes, at these sizes.

The kernel is found by name and shape: the program's Pallas call whose
result is the (slots, vocab) f32 logits."""

from bench import costs
from bench.metrics_common import first_device, pallas_calls


def read(run):
    dev = first_device(run)
    if run.peaks is None:
        return None
    cfg, slots = run.cfg, run.mix["slots"]
    calls = pallas_calls(dev, f"f32[{slots},{cfg['vocab_size']}]")
    if not calls:
        return None
    cost = costs.fused_decode_cost(slots, cfg["d_model"], cfg["head"],
                                   cfg["vocab_size"])
    least = max(cost["flops"] / run.peaks["bf16_flops"],
                cost["bytes"] / run.peaks["hbm_bytes_per_s"])
    spent = sum(e - s for _, s, e, _ in calls) * 1e-9
    return 100.0 * len(calls) * least / spent
