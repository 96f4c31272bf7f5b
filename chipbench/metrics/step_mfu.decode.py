"""Model FLOPs of the window's decode steps (each decoded token through
every layer and the LM head, attention over its real context; the
benchmark's own count) over those steps' wall time times the chip's
peak bf16 rate."""
import flops


def value(run):
    steps = [s for s in run.window_steps()
             if s.decode_ctxs and not s.prefill_lens]
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    work = sum(flops.decode_flops(run.dims, s.decode_ctxs) for s in steps)
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"])
