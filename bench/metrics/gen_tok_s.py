"""Generated tokens emitted in the window over the window's length."""


def read(run):
    return run.delta("tokens") / (run.window[1] - run.window[0])
