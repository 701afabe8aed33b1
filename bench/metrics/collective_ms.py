"""Device time of the collectives (the ownership swaps) per step on the
busiest device, in ms."""


def read(run):
    t = run.trace
    if t is None:
        return None
    ns = t.kind_ns(t.busiest(), 'collective')
    return ns / 1e6 / t.steps if ns > 0 else None
