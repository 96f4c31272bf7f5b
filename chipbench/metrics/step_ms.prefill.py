"""Mean wall time of the window's steps that ran a prefill (an exclusive
prefill stalls every decoding request for this long)."""


def value(run):
    steps = [s for s in run.window_steps() if s.prefill_lens]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
