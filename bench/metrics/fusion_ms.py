"""Device time per step, on the busiest device, of the compute ops that
are neither Mosaic kernels nor collectives (XLA fusions, relayouts,
twiddles, Hermitian combines), in ms."""


def read(run):
    t = run.trace
    if t is None:
        return None
    ns = t.kind_ns(t.busiest(), 'compute')
    return ns / 1e6 / t.steps if ns > 0 else None
