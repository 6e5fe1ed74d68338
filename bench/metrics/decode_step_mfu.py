"""Model operations of the decode steps in the traced span (the backbone's
from the family's reference module: 2 per weight per active row, the WKV
recurrence or attention over live positions; and the head's,
``bench/costs.py``) over the megastep programs' device time times the
chip's bf16 peak."""

from bench import costs
from bench.metrics_common import first_device, program_ns


def read(run):
    dev = first_device(run)
    ns = program_ns(dev, "megastep")
    rows = run.delta("active_slot_steps")
    if not dev or not ns or not rows or run.peaks is None:
        return None
    flops = costs.decode_flops(run.cfg, run.ref, rows,
                               run.delta("live_positions"))
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
