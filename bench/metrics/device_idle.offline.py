"""Share of the traced span in which no operation ran on the device: one
minus the union of the op intervals over the span's length."""

from bench.metrics_common import first_device


def read(run):
    dev = first_device(run)
    if not dev:
        return None
    return 100.0 * (1.0 - dev["busy_ns"] / run.trace["window_ns"])
