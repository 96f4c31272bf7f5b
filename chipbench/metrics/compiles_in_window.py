"""XLA compiles (and persistent-cache loads) during the window, from
JAX's monitoring events: every program, the executor's steps, its pool
scatters and copies, and eager slices alike. Set-up should leave none."""


def value(run):
    return float(run.compiles.count(run.w0, run.w1))
