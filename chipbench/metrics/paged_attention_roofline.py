"""The paged decode-attention kernel's share of its roofline: the least
time the chip needs for the FLOPs and KV bytes the live contexts of the
traced decode steps require (the benchmark's own count, padded table
slots left out), over the kernel's device time in the trace."""
import flops

KERNEL = "paged_attention"


def value(run):
    tr = run.trace
    if tr is None:
        return None
    secs = tr.kernel_seconds(KERNEL)
    steps = [s for s in run.steps if s.decode_ctxs and not s.prefill_lens
             and s.t0 >= tr.host_t0 and s.t1 <= tr.host_t1]
    if secs <= 0 or not steps:
        return None
    least = 0.0
    for s in steps:
        f, b = flops.paged_attention_cost(run.dims, s.decode_ctxs)
        least += max(f / run.peaks["bf16_flops_per_s"],
                     b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
