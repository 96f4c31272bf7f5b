"""The span reduction (`spantrace.py`) and its four readers: the jitted
programs of the recorded TPU trace, span self time and stats on a small
nesting by hand, the readers on hand-built summaries, and a traced CPU
run reduced with `spantrace.reduce_dir` in place of `trace.reduce_dir`."""
from __future__ import annotations

import copy
import gzip
import os
import shutil

import pytest

import common
import run
import small
import spantrace
import trace as tr
from harness import CompileLog, RunData
from test_yardstick import _req

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ["host_ms.sched", "host_ms.exec", "device_ms.decode",
           "kv_moved_mb_per_req"]


def test_recorded_trace_programs_and_spans(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "granite_decode3.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    s = spantrace.reduce_file(str(path), window_s=1.0)
    # the accepted reduction's fields are as `trace.reduce_file` gives them
    base = tr.reduce_file(str(path), window_s=1.0)
    assert {k: getattr(s, k) for k in vars(base)} == vars(base)
    # the "XLA Modules" line: each jitted program's calls and device time
    assert s.modules["jit__paged_decode"]["count"] == 3
    assert s.modules["jit__paged_decode"]["s"] == \
        pytest.approx(0.645447, rel=1e-5)
    assert s.modules["jit__argmax"]["count"] == 3
    assert set(s.modules) == {"jit__paged_decode", "jit__argmax"}
    # the three bench.step spans, with no child span inside them
    step = s.spans["bench.step"]
    assert step["count"] == 3
    assert step["self_s"] == pytest.approx(step["total_s"])
    assert set(s.spans) == {"bench.step"}


def test_span_table_self_time_and_stats_by_hand():
    # one host line: a step 0-100 ns holding an admission 10-50 ns (with
    # an allocation 20-30 inside it) and a copy 60-90 ns; a second line
    # with one more copy, 0-40 ns
    line = [(0, 100, "sched.step", {"step": 1}),
            (10, 50, "sched.admit", {"waiting": 3, "stop_gate": "none"}),
            (20, 30, "sched.admit.alloc", {"rid": "r1", "retained": 2}),
            (60, 90, "exec.kv.copy", {"src": "device", "dst": "host",
                                      "blocks": 2, "bytes": 200})]
    other = [(0, 40, "exec.kv.copy", {"src": "host", "dst": "device",
                                      "blocks": 3, "bytes": 300})]
    t = spantrace.span_table([line, other])
    # step: 100 less the admission (40) and the copy (30) on its line
    assert t["sched.step"]["self_s"] == pytest.approx(30e-9)
    assert t["sched.step"]["total_s"] == pytest.approx(100e-9)
    assert t["sched.admit"]["self_s"] == pytest.approx(30e-9)
    assert t["sched.admit.alloc"]["self_s"] == pytest.approx(10e-9)
    # the other line's copy is nobody's child
    cp = t["exec.kv.copy"]
    assert cp["count"] == 2
    assert cp["self_s"] == pytest.approx(70e-9)
    assert cp["stats"] == {"blocks": 5, "bytes": 500}
    assert cp["values"] == {"src": {"device": 1, "host": 1},
                            "dst": {"host": 1, "device": 1}}
    assert cp["by"]["dst=host"] == {"blocks": 2, "bytes": 200}
    assert cp["by"]["src=host"] == {"blocks": 3, "bytes": 300}
    assert t["sched.admit"]["values"]["stop_gate"] == {"none": 1}
    assert t["sched.admit.alloc"]["stats"] == {"retained": 2}
    assert t["sched.step"]["stats"] == {"step": 1}


def _traced_run(spans=None, modules=None, n_reqs=4):
    run = RunData(cell={"name": "x"}, dims={}, peaks={},
                  reqs=[_req(f"r{i}", 0.5, [1.0, 1.1])
                        for i in range(n_reqs)],
                  steps=[], w0=0.0, w1=10.0, setup_s=1.0,
                  compiles=CompileLog())
    run.trace = spantrace.SpanTraceSummary(
        busy_s=1.0, window_s=10.0, ops={}, kernels={}, gaps={},
        n_devices=1, spans=spans or {}, modules=modules or {})
    return run


def _row(count, self_s, stats=None, by=None):
    return {"count": count, "total_s": self_s, "self_s": self_s,
            "stats": stats or {}, "values": {}, "by": by or {}}


def test_span_readers_by_hand():
    read = common.metric_reader
    spans = {
        "sched.step": _row(4, 0.008),
        "sched.admit": _row(4, 0.020),
        "sched.admit_waiting": _row(4, 0.004),       # the harness's span
        "exec.decode.prep": _row(8, 0.012),
        "exec.decode.wait": _row(4, 1.600),
        "exec.write_layer": _row(80, 0.004),         # the harness's span
        "exec.kv.write": _row(80, 0.024,
                              stats={"bytes": 8_000_000},
                              by={"tier=host": {"bytes": 3_000_000},
                                  "tier=device": {"bytes": 5_000_000}}),
        "exec.kv.copy": _row(10, 0.002, stats={"bytes": 5_000_000}),
        "bench.step": _row(4, 0.001),
    }
    modules = {"jit_serve_decode": {"count": 4, "s": 1.6},
               "jit_kv_write": {"count": 80, "s": 0.08}}
    run = _traced_run(spans, modules)
    # (8 + 20 + 4) ms of sched.* self time over 4 steps
    assert read("host_ms.sched")(run) == pytest.approx(8.0)
    # (12 + 4 + 24 + 2) ms of exec.* self time, waits left out, 4 steps
    assert read("host_ms.exec")(run) == pytest.approx(10.5)
    assert read("device_ms.decode")(run) == pytest.approx(400.0)
    # 3 MB written to the host tier and 5 MB copied, over 4 requests
    assert read("kv_moved_mb_per_req")(run) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", READERS)
def test_span_readers_find_nothing_without_program_spans(metric):
    """A program without the spans and program names (the harness's own
    spans only), the accepted reduction's summary, which has no such
    fields, or an untraced run: each reads None."""
    read = common.metric_reader(metric)
    run = _traced_run({"sched.admit_waiting": _row(4, 0.01),
                       "exec.copy_blocks": _row(3, 0.01)},
                      {"jit__paged_decode": {"count": 3, "s": 0.6}})
    assert read(run) is None
    run.trace = tr.TraceSummary(busy_s=1.0, window_s=10.0, ops={},
                                kernels={}, gaps={}, n_devices=1)
    assert read(run) is None
    run.trace = None
    assert read(run) is None


def test_traced_run_reads_the_program_spans(monkeypatch):
    """A traced CPU run reduced by `spantrace` reads the program's host
    spans; the CPU has no TPU plane, so the device-time readers say
    nothing."""
    args, bench, *rest = small.tiny_inputs(2**31 + 11, monkeypatch)
    args.trace = 1
    bench = copy.deepcopy(bench)
    bench["per_layer"] += [{"name": m, "unit": "ms"} for m in READERS]
    monkeypatch.setattr(tr, "reduce_dir", spantrace.reduce_dir)
    res, _ = run.run_cell(args, bench, *rest, run.process_start())
    assert res["correct"] is True
    m = res["metrics"]
    assert m["host_ms.sched"]["value"] > 0
    assert m["host_ms.exec"]["value"] > 0
    assert m["kv_moved_mb_per_req"]["value"] > 0
    assert "device_ms.decode" not in m and "device_idle_pct" not in m
