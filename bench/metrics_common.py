"""Helpers the metric readers share: the first traced device, the device
time of programs by name and by model scope, and the program's kernels by
name and shape."""


def first_device(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    return next(iter(run.trace["devices"].values()))


def program_ns(dev, needle: str) -> float:
    """Device time of the programs whose name holds ``needle`` (the
    speculative megastep excluded)."""
    if not dev:
        return 0.0
    return sum(ns for name, ns in dev["modules"].items()
               if needle in name and "spec_" not in name)


def scope_ns(dev, program: str, scope: str) -> float:
    """Device self time of the program ``program`` (e.g. ``jit_megastep``)
    under the model scope ``scope`` (one component of its operations'
    ``tf_op`` path, e.g. ``head``)."""
    if not dev:
        return 0.0
    return dev["scopes"].get(program, {}).get(scope, 0.0)


def pallas_calls(dev, out_shape: str):
    """Pallas kernel events (``%_pallas.<n> = <out_shape> custom-call``)
    whose result is ``out_shape``, e.g. ``f32[128,65536]``."""
    if not dev:
        return []
    return [ev for ev in dev["op_events"]
            if ev[0].startswith("%_pallas")
            and ev[0].split("=", 1)[-1].strip().startswith(out_shape)]
