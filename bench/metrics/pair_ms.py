"""Window time over the steps completed in it, in ms (host clock)."""


def read(run):
    return run.window_s / run.steps * 1e3
