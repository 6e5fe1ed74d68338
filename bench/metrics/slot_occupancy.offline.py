"""Busy slots per decode step over all slots, from the engine's counters
(active_slot_steps over decode_steps x slots) across the traced span."""


def read(run):
    steps = run.delta("decode_steps")
    if not steps:
        return None
    return run.delta("active_slot_steps") / (steps * run.mix["slots"])
