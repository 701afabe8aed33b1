"""The part of the collectives' device time per step, on the busiest
device, during which no compute op or kernel runs there, in ms."""


def read(run):
    t = run.trace
    if t is None:
        return None
    dev = t.busiest()
    if t.kind_ns(dev, 'collective') <= 0:
        return None
    return t.exposed_ns(dev, 'collective') / 1e6 / t.steps
