"""95th percentile of the window's step times, dispatch of the first
call to the ready of the last, in ms (host clock). Python's
``statistics.quantiles`` with the 'inclusive' method over every step."""
import statistics


def read(run):
    if run.steps < 2:
        return None
    return statistics.quantiles(run.step_s, n=20,
                                method='inclusive')[18] * 1e3
