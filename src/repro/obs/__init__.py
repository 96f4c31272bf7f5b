"""Observability layer: counter/gauge registry (always on, pure dict
ops), profiler spans (always compiled in, recorded while the profiler
runs), event tracer with exact TTFT attribution on the scheduler's
virtual clock, and Perfetto/Prometheus exporters.

The registry and the span helper are imported eagerly (schedulers route
their counters through the one and open their spans with the other);
the tracer and exporters are PEP 562 lazy re-exports so a
`trace=False` run never imports them — the zero-overhead-when-off
contract tests/test_obs.py pins by asserting ``repro.obs.trace`` stays
out of ``sys.modules``.
"""
from __future__ import annotations

import importlib

from repro.obs.registry import MetricsRegistry
from repro.obs.spans import SPAN_NAMES, span

__all__ = ["MetricsRegistry", "SPAN_NAMES", "span", "Tracer", "EVENT_TYPES",
           "ATTRIBUTION_CAUSES", "perfetto_trace", "prometheus_text",
           "write_trace"]

_LAZY = {
    "Tracer": "repro.obs.trace",
    "EVENT_TYPES": "repro.obs.trace",
    "ATTRIBUTION_CAUSES": "repro.obs.trace",
    "perfetto_trace": "repro.obs.export",
    "prometheus_text": "repro.obs.export",
    "write_trace": "repro.obs.export",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    return getattr(importlib.import_module(mod), name)
