"""Offload and reload entries the offload ledger gained during the
window, per request due in it: how often layer-wise KV moves between
the tiers."""


def value(run):
    counted = run.counted()
    before = [s.moves for s in run.steps if s.t1 <= run.w0]
    inside = run.window_steps()
    if not counted or not inside:
        return None
    start = before[-1] if before else 0
    return (inside[-1].moves - start) / len(counted)
