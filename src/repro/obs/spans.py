"""Profiler spans: the engine's wall-clock record.

`span(name, **stats)` is the one way program code opens a span. It is a
`jax.profiler.TraceAnnotation`, so a span lands on the host thread's
line of the profiler trace, on the same clock as the device's op and
module events, and carries its keyword arguments as stats (counts,
bytes, request ids). Nesting on the host thread gives the parent. A
stat known only at the end of the span is added with
``sp.set_metadata(...)`` before the block exits.

Spans are always compiled in. With no profiler running, `span` costs
one profiler check and returns a shared no-op; an operator records
them by running the profiler (`jax.profiler.trace`, or the TensorBoard
and Perfetto tooling around it). No scheduling decision reads a clock
or a span.

Names (`SPAN_NAMES`; docs/ARCHITECTURE.md "Observability" lists each
with its stats, enforced by tools/check_docs.py) start with the layer
they time: ``sched.`` the scheduler and its KV manager, ``exec.`` the
executor. A ``*.wait`` span covers only the host blocking on a device
result, and no other name ends in ``.wait``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SPAN_NAMES = (
    # scheduler and KV manager (host)
    "sched.step", "sched.admit", "sched.admit.budget",
    "sched.admit.alloc", "sched.prefill", "sched.select",
    "sched.kv.evict", "sched.kv.reload", "sched.retire",
    # executor: launches, waits, pool traffic
    "exec.prefill.launch", "exec.prefill.wait",
    "exec.decode.prep", "exec.decode.launch", "exec.decode.wait",
    "exec.chunk.launch", "exec.chunk.wait",
    "exec.mixed.prep", "exec.mixed.launch", "exec.mixed.wait",
    "exec.kv.write", "exec.kv.copy", "exec.kv.gather",
)


class _Off:
    """The span handed out while no profiler runs: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc: object) -> None:
        return None

    def set_metadata(self, **stats: object) -> None:
        return None


_OFF = _Off()


def span(name: str, **stats: object):
    """A profiler span named `name` with `stats`, to use as a context
    manager; a no-op unless the profiler is recording."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **stats)
