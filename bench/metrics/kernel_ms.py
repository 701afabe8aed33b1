"""Device time of the Mosaic (Pallas) kernels per step on the busiest
device, in ms: the union of their op intervals, whatever their names."""


def read(run):
    t = run.trace
    if t is None:
        return None
    ns = t.kind_ns(t.busiest(), 'kernel')
    return ns / 1e6 / t.steps if ns > 0 else None
