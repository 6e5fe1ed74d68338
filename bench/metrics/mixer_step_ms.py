"""Device self time under the model scope ``mixer`` (each layer's token
mixing: attention with its cache write, or the rwkv time-mix with its
state update) in the decode megastep program, per decode step, over the
traced span.  Nothing to read in a trace whose operations carry no scope."""

from bench.metrics_common import first_device, scope_ns


def read(run):
    steps = run.delta("decode_steps")
    ns = scope_ns(first_device(run), "jit_megastep", "mixer")
    if not steps or not ns:
        return None
    return ns * 1e-6 / steps
