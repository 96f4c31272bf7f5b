"""Device time of one decode step: the seconds of the jitted decode
program (`jit_serve_decode` on the device trace's "XLA Modules" line)
in the traced window, over its calls there."""

PROGRAM = "jit_serve_decode"


def value(run):
    # the programs come from `spantrace.reduce_dir`; `trace.reduce_dir`
    # gives none
    m = (getattr(run.trace, "modules", None) or {}).get(PROGRAM)
    if not m or not m["count"]:
        return None
    return 1e3 * m["s"] / m["count"]
