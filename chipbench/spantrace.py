"""The trace reduction of `trace.py`, with the program's own spans and
jitted programs tabled beside it, for the span readers
(`metrics/host_ms.*`, `metrics/device_ms.decode`,
`metrics/kv_moved_mb_per_req`).

`spans`: per host span name with the prefixes `trace.SPAN_PREFIXES`, its
count, total and self seconds (a span's time less the union of its child
spans on the same host line), the sum of each numeric stat, the count of
each value of each string stat, and the numeric sums split by string
stat value. `modules`: per jitted program on the device's "XLA Modules"
line (jit name without the `(hash)` suffix), its calls and device
seconds, averaged over the TPU planes.

`run.py` reduces a traced run with `trace.reduce_dir`; with
`spantrace.reduce_dir` in its place the span readers read a value.
Without these fields, each of them reads None.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List

import trace

MODULES_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class SpanTraceSummary(trace.TraceSummary):
    # host span name -> {"count", "total_s", "self_s", "stats": {stat:
    # sum}, "values": {stat: {value: count}}, "by": {"stat=value":
    # {stat: sum}}}
    spans: Dict[str, dict] = dataclasses.field(default_factory=dict)
    # jitted program ("XLA Modules" name) -> {"count", "s"} per device
    modules: Dict[str, dict] = dataclasses.field(default_factory=dict)


def span_table(lines) -> Dict[str, dict]:
    """Table the host spans by name. `lines` holds, per host line, its
    spans as (start_ns, end_ns, name, stats). A span's children are the
    spans it contains on its own line."""
    out: Dict[str, dict] = {}
    for events in lines:
        events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
        children: Dict[int, list] = defaultdict(list)
        open_: List[int] = []
        for i, (a, b, _, _) in enumerate(events):
            while open_ and events[open_[-1]][1] <= a:
                open_.pop()
            if open_ and b <= events[open_[-1]][1]:
                children[open_[-1]].append((a, b))
            open_.append(i)
        for i, (a, b, name, stats) in enumerate(events):
            row = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0, "stats": {},
                                        "values": {}, "by": {}})
            inner = sum(hi - lo for lo, hi in trace._union(children[i]))
            row["count"] += 1
            row["total_s"] += (b - a) / 1e9
            row["self_s"] += (b - a - inner) / 1e9
            nums = {k: v for k, v in stats.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
            for k, v in nums.items():
                row["stats"][k] = row["stats"].get(k, 0) + v
            for k, v in stats.items():
                if not isinstance(v, str):
                    continue
                seen = row["values"].setdefault(k, {})
                seen[v] = seen.get(v, 0) + 1
                by = row["by"].setdefault(f"{k}={v}", {})
                for nk, nv in nums.items():
                    by[nk] = by.get(nk, 0) + nv
    return out


def read_file(path: str) -> tuple:
    """(spans, modules) of a `.xplane.pb` file, as the fields above."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host_lines = []
    modules: Dict[str, dict] = {}
    n_devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == MODULES_LINE]
            n_devices += bool(lines)
            for ln in lines:
                for e in ln.events:
                    m = modules.setdefault(_HASH.sub("", e.name),
                                           {"count": 0, "s": 0.0})
                    m["count"] += 1
                    m["s"] += e.duration_ns / 1e9
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                line = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                        for e in ln.events
                        if e.name.startswith(trace.SPAN_PREFIXES)]
                if line:
                    host_lines.append(line)
    n = max(n_devices, 1)
    return span_table(host_lines), {
        k: {"count": v["count"] / n, "s": v["s"] / n}
        for k, v in modules.items()}


def reduce_file(path: str, window_s: float) -> SpanTraceSummary:
    base = trace.reduce_file(path, window_s)
    spans, modules = read_file(path)
    return SpanTraceSummary(**vars(base), spans=spans, modules=modules)


def reduce_dir(trace_dir: str, window_s: float) -> SpanTraceSummary:
    return reduce_file(trace.find_xplane(trace_dir), window_s)
