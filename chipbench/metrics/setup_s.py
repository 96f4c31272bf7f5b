"""Set-up: process start to window start (weights, pool sizing, warm-up
of every shape, and the warm-in)."""


def value(run):
    return run.setup_s
