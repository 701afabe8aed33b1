"""Seconds from the start of the process to the start of the window:
jax and TPU start-up, planning, compiles or compile-cache loads, the
on-device input and the warm-up steps (host clock)."""


def read(run):
    return run.setup_s
