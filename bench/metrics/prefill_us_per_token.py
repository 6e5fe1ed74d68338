"""Prefill device time per prompt token: the time of the program
``jit_prefill`` in the traced span over the prompt tokens of the requests
admitted in it (the client's ``prompt_tokens`` counter).  Nothing to read
in a program that does not name the prefill."""

from bench.metrics_common import first_device


def read(run):
    dev = first_device(run)
    ns = dev["modules"].get("jit_prefill", 0.0) if dev else 0.0
    tokens = run.delta("prompt_tokens")
    if not ns or not tokens:
        return None
    return ns * 1e-3 / tokens
