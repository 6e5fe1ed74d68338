"""Share of the programs' device time in the traced span that goes to
admitting requests: every program but the decode megasteps (prefill,
inserting the new rows into the pool, sampling their first tokens)."""

from bench.metrics_common import first_device


def read(run):
    dev = first_device(run)
    total = sum(dev["modules"].values()) if dev else 0.0
    if not total:
        return None
    admit = sum(ns for name, ns in dev["modules"].items()
                if "megastep" not in name)
    return 100.0 * admit / total
