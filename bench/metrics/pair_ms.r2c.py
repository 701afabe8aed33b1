"""Window time over the steps completed in it, in ms (host clock): the
``pair_ms`` of the real-input cell, under a bound of its own (its runs
spread wider; see PERF.md)."""


def read(run):
    return run.window_s / run.steps * 1e3
