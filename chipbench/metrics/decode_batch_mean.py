"""Requests served per decode-only step in the window: the decode batch
the scheduler forms."""


def value(run):
    steps = [s for s in run.window_steps()
             if s.decode_ctxs and not s.prefill_lens]
    if not steps:
        return None
    return sum(len(s.decode_ctxs) for s in steps) / len(steps)
