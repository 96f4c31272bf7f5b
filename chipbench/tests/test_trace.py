"""The trace reduction: interval arithmetic and gap attribution by hand,
and the reduction of a small trace recorded on a TPU v5e (three decode
steps of granite-3-2b, each under a `bench.step` span)."""
from __future__ import annotations

import gzip
import os
import shutil

import pytest

import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == \
        [(0, 3), (5, 8), (10, 11)]


def test_gap_goes_to_innermost_span():
    spans = [(0, 100, "bench.step"), (20, 40, "exec.decode"),
             (60, 70, "sched.admit_waiting")]
    # gap 10..80 ns: 10-20 and 40-60 and 70-80 in bench.step (40 ns),
    # 20-40 in exec.decode (20 ns), 60-70 in admission (10 ns)
    out = tr._attribute([(10, 80)], spans)
    assert out["bench.step"] == pytest.approx(40e-9)
    assert out["exec.decode"] == pytest.approx(20e-9)
    assert out["sched.admit_waiting"] == pytest.approx(10e-9)
    # a gap no span covers is the host's other work
    assert tr._attribute([(200, 250)], spans) == \
        {"host.other": pytest.approx(50e-9)}


def test_recorded_trace_reduces(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "granite_decode3.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    s = tr.reduce_file(str(path), window_s=1.0)
    assert s.n_devices == 1
    # three decode steps of batch 32 over 1500-token contexts: 40 layers
    # x 3 steps of the paged kernel, 1.83 ms each, and the device busy
    # for 0.645 s in all
    assert s.busy_s == pytest.approx(0.645456658, rel=1e-6)
    assert s.kernels == {"paged_attention_pallas": pytest.approx(
        0.219761155, rel=1e-6), "custom-call": pytest.approx(1.7e-7)}
    assert s.kernel_seconds("paged_attention") == \
        pytest.approx(0.219761155, rel=1e-6)
    # the step copies the whole device pool once per layer
    b = s.breakdown()
    assert b["device_ops"][0][0] == "copy bf16[2001,2,8,128,64]"
    assert b["device_ops"][0][1] == pytest.approx(0.398171253, rel=1e-6)
    assert len(b["device_ops"]) == 10
    # the idle time between steps is the host outside the bench.step span
    assert dict(b["idle_gaps"]) == {
        "host.other": pytest.approx(0.021264721, rel=1e-6),
        "bench.step": pytest.approx(0.007033964, rel=1e-6)}
    # ops add up to at least the busy union (overlaps count once there)
    assert sum(s.ops.values()) >= s.busy_s
