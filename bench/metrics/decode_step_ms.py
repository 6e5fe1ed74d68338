"""Device time of the decode megastep programs per decode step, from the
trace of the traced span."""

from bench.metrics_common import first_device, program_ns


def read(run):
    dev = first_device(run)
    steps = run.delta("decode_steps")
    ns = program_ns(dev, "megastep")
    if not dev or not steps or not ns:
        return None
    return ns * 1e-6 / steps
