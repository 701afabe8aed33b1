"""The algorithm's least time per step over the busiest device's busy
time per step, in %. The least time is max(nominal flops / bf16 peak,
minimum bytes / HBM peak) of the step's transforms (``bench.work``),
never a count of the implementation's passes."""
from bench import work


def read(run):
    t = run.trace
    if t is None or run.peak is None:
        return None
    busy = t.busy_ns(t.busiest()) / 1e9 / t.steps
    if busy <= 0:
        return None
    bound, _ = work.step_bound(run.work, run.calls, run.peak)
    return 100.0 * bound / busy
