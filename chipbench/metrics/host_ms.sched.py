"""Host time of the scheduler and its KV manager per engine step: the
self time (a span's time less its child spans') of every `sched.*` span
in the traced window, over the `sched.step` spans there. The harness's
own `sched.*` spans count too: their self time is scheduler host work
that no program span covers."""


def value(run):
    # the spans come from `spantrace.reduce_dir`; `trace.reduce_dir`
    # gives none
    spans = getattr(run.trace, "spans", None) or {}
    steps = spans.get("sched.step", {}).get("count", 0)
    if not steps:
        return None
    return 1e3 * sum(v["self_s"] for k, v in spans.items()
                     if k.startswith("sched.")) / steps
