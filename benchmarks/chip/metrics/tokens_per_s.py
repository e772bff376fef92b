"""Live (non-pad) target tokens trained in the window over the window's
host-clock length, from the first dispatch to the end of the last step,
summed over the cell's chips.  Counted on the host from the pack index."""


def read(run):
    if run.trace is not None or not run.window_s:
        return None
    return run.window_tokens / run.window_s
