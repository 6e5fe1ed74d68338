"""Process start to the first timed request: imports, weights, head,
warm-up (compiles or cache loads) and, for a backlog, filling the slots."""


def read(run):
    return run.setup_s
