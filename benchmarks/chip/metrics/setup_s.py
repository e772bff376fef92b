"""Seconds from process start to the start of the window: imports, device
start, corpus, weights, the compile (from the cache after a checkout's first
run) and the checked warm-up steps."""


def read(run):
    if run.trace is not None:
        return None
    return run.setup_s
