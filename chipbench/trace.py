"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: device busy time, time per device operation, time in one kernel,
and the device's idle gaps attributed to what the host was doing.

Busy time is the union of the intervals in which an operation ran on a
TPU's "XLA Ops" line, averaged over the TPU planes. An idle gap is a
stretch of the window between busy intervals; each part of it is
credited to the innermost host span (the harness's `sched.*`, `exec.*`
and `bench.*` annotations) that covers it, or to `host.other`.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIXES = ("sched.", "exec.", "bench.")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[._]\d+$")


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    ops: Dict[str, float]            # device op name -> seconds
    kernels: Dict[str, float]        # kernel name -> seconds
    gaps: Dict[str, float]           # host span -> idle seconds
    n_devices: int
    host_t0: float = 0.0             # host clock at the trace's start
    host_t1: float = 0.0             # and at its stop

    def kernel_seconds(self, kernel: str) -> float:
        return sum(v for k, v in self.kernels.items() if kernel in k)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _op_name(event) -> str:
    """`kind shape` of an XLA op event named `%kind.N = shape{layout} ...`
    (a Pallas kernel's kind is its name), or the event's own name."""
    head, _, rest = event.name.partition(" = ")
    if not rest:
        return event.name[:80]
    kind = _SUFFIX.sub("", head.lstrip("%"))
    return f"{kind} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def _kernel_name(event):
    """The kind of an op that is a Pallas kernel call, else None."""
    head, _, rest = event.name.partition(" = ")
    if "custom-call(" not in rest:
        return None
    return _SUFFIX.sub("", head.lstrip("%"))


def _attribute(gaps, spans) -> Dict[str, float]:
    """Credit each gap's time to the innermost covering host span."""
    out: Dict[str, float] = defaultdict(float)
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    for a, b in gaps:
        # spans that start before the gap ends and end after it starts
        i = bisect.bisect_right(starts, b)
        cover = [(s, e, n) for s, e, n in spans[:i] if e > a]
        cuts = sorted({a, b} | {max(a, s) for s, _, _ in cover}
                      | {min(b, e) for _, e, _ in cover})
        for lo, hi in zip(cuts, cuts[1:]):
            if hi <= lo:
                continue
            mid = (lo + hi) / 2
            inner = [(e - s, n) for s, e, n in cover if s <= mid < e]
            name = min(inner)[1] if inner else "host.other"
            out[name] += (hi - lo) / 1e9
    return dict(out)


def reduce_file(path: str, window_s: float) -> TraceSummary:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append(list(ops[0].events))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.end_ns, e.name))
    busy = 0.0
    ops: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for events in devices:
        iv = [(e.start_ns, e.end_ns) for e in events]
        merged = _union(iv)
        busy += sum(b - a for a, b in merged) / 1e9
        for e in events:
            ops[_op_name(e)] += e.duration_ns / 1e9
            k = _kernel_name(e)
            if k:
                kernels[k] += e.duration_ns / 1e9
        idle = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for k, v in _attribute(idle, spans).items():
            gaps[k] += v
    n = max(len(devices), 1)
    return TraceSummary(
        busy_s=busy / n, window_s=window_s,
        ops={k: v / n for k, v in ops.items()},
        kernels={k: v / n for k, v in kernels.items()},
        gaps={k: v / n for k, v in gaps.items()},
        n_devices=len(devices))


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_dir(trace_dir: str, window_s: float) -> TraceSummary:
    return reduce_file(find_xplane(trace_dir), window_s)
