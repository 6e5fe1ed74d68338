"""Device self time under the model scope ``head`` (the logit head: the
dense unembed, or the sketch head with its fused kernel) in the decode
megastep program, per decode step, over the traced span.  Nothing to read
in a trace whose operations carry no scope."""

from bench.metrics_common import first_device, scope_ns


def read(run):
    steps = run.delta("decode_steps")
    ns = scope_ns(first_device(run), "jit_megastep", "head")
    if not steps or not ns:
        return None
    return ns * 1e-6 / steps
