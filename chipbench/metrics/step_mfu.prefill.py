"""Model FLOPs of the real prompt tokens of the window's prefill steps
(the benchmark's own count, padding left out) over those steps' wall
time times the chip's peak bf16 rate."""
import flops


def value(run):
    steps = [s for s in run.window_steps() if s.prefill_lens]
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    work = sum(flops.prefill_flops(run.dims, p)
               for s in steps for p in s.prefill_lens)
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"])
