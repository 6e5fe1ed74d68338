"""Device time per slot write: the mean duration of the runs of the
programs ``jit_slot_insert`` (an admission's rows into the pool) and
``jit_slot_reset`` (a retired slot's rows zeroed) that start in the traced
span.  Writing only the slot's rows takes well under a millisecond at the
cells' sizes; rewriting the whole pool reads several.  Nothing to read in a
program that does not name these programs."""

from bench.metrics_common import first_device
from bench.trace_reduce import program_name

PROGRAMS = ("jit_slot_insert", "jit_slot_reset")


def read(run):
    dev = first_device(run)
    runs = [e - s for name, s, e, _ in (dev["module_events"] if dev else [])
            if program_name(name) in PROGRAMS]
    if not runs:
        return None
    return sum(runs) * 1e-6 / len(runs)
