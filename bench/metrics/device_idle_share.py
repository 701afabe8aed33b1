"""Share of the traced window in which no operation ran on the busiest
device, in %: 1 - (union of its op intervals) / window. The window is
the host's ``bench.window`` annotation."""


def read(run):
    t = run.trace
    if t is None:
        return None
    dev = t.busiest()
    return 100.0 * (1.0 - t.busy_ns(dev) / (t.window[1] - t.window[0]))
