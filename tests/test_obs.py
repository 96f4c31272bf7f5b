"""Observability layer (src/repro/obs): registry, tracer, exporters.

What is pinned here:

  * registry semantics — labelled counters/gauges, Counter-shaped views,
    Prometheus-rendered snapshots, cross-replica snapshot merging;
  * EXACT TTFT attribution — for every finished request, on both
    backends and across the scheduling axes, the cause-labelled
    intervals of `Tracer.ttft_breakdown` sum to the measured TTFT
    bit-for-bit (the telescoping-partition contract trace.py documents),
    including through a vLLM recompute-preemption reopen;
  * event coverage — every member of EVENT_TYPES is emitted by some
    reachable scenario (lifecycle, preemption, shed, cancel, cluster
    faults), so the documented vocabulary never rots;
  * zero overhead when off — a `trace=False` run never imports
    `repro.obs.trace` (subprocess-checked) and is BIT-IDENTICAL to an
    untraced run on every scheduling arm;
  * export validity — the Chrome-trace JSON loads, timestamps are
    monotone per track, durations non-negative, and the Prometheus text
    round-trips the snapshot.
"""
import json
import random
import subprocess
import sys

import pytest

from repro.configs.llama2_7b import CONFIG as LLAMA2_7B
from repro.core import DEVICE, HOST
from repro.obs import ATTRIBUTION_CAUSES, EVENT_TYPES, MetricsRegistry
from repro.obs.export import perfetto_trace, prometheus_text
from repro.obs.trace import Tracer
from repro.serving.cluster import ClusterSession
from repro.serving.costmodel import L20
from repro.serving.faults import FaultPlan
from repro.serving.request import Request
from repro.serving.scheduler import ServeConfig
from repro.serving.session import ServingSession
from repro.serving.sim import ServingSimulator
from repro.serving.workload import multi_tenant

EPS = 1e-9


def _sim(**kw):
    base = dict(policy="layerkv", num_device_blocks=2048,
                num_host_blocks=1 << 14, trace=True)
    base.update(kw)
    return ServingSimulator(LLAMA2_7B, L20, ServeConfig.for_sim(**base))


def _reqs(n=10, prompt=256, output=32, rate=8.0, seed=0):
    rng = random.Random(seed)
    t, out = 0.0, []
    for i in range(n):
        t += rng.expovariate(rate)
        out.append(Request(rid=f"r{i}", prompt_len=prompt,
                           output_len=output, arrival=t))
    return out


def _assert_exact(done, tracer):
    bks = tracer.breakdowns()
    for r in done:
        assert r.rid in bks, f"{r.rid} has no finalized breakdown"
        total = sum(bks[r.rid].values())
        assert abs(total - r.ttft) < EPS, \
            f"{r.rid}: sum {total} != ttft {r.ttft} ({bks[r.rid]})"
        assert set(bks[r.rid]) <= set(ATTRIBUTION_CAUSES)


# ------------------------------------------------------------- registry ---

def test_registry_counters_gauges_and_views():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2.0)
    reg.inc("b", kind="x")
    reg.inc("b", 3.0, kind="y")
    reg.set_gauge("g", 7.0, tier="device")
    reg.set_gauge("g", 5.0, tier="device")      # last write wins
    assert reg.get("a") == 3.0
    assert reg.get("b", kind="y") == 3.0
    assert reg.get("never") == 0.0              # reads never create
    assert reg.total("b") == 4.0
    assert reg.counter_view("b", "kind") == {"x": 1, "y": 3}
    snap = reg.snapshot()
    assert snap["a"] == 3.0
    assert snap['b{kind="y"}'] == 3.0
    assert snap['g{tier="device"}'] == 5.0
    stamped = reg.snapshot(replica="2")
    assert stamped['b{kind="y",replica="2"}'] == 3.0
    merged = MetricsRegistry.merge_snapshots(snap, snap)
    assert merged["a"] == 6.0


def test_prometheus_text_renders_sorted_lines():
    txt = prometheus_text({"b": 2.0, 'a{k="v"}': 1.5})
    assert txt == 'a{k="v"} 1.5\nb 2\n'
    assert prometheus_text({}) == ""


# ------------------------------------------------------ exact attribution ---

@pytest.mark.parametrize("policy", ["vllm", "layerkv"])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["exclusive", "chunked"])
def test_sim_ttft_decomposition_exact(policy, chunked):
    """The acceptance contract: sum of attributed intervals == measured
    TTFT, exactly, for every request, on both policies and both step
    semantics."""
    sim = _sim(policy=policy, chunked=chunked)
    sim.run(_reqs())
    assert len(sim.done) == 10
    _assert_exact(sim.done, sim.core.tracer)


def test_sim_decomposition_exact_under_device_pressure():
    """A pool small enough to block admission: waits get attributed to
    gate causes (not arrival_sync) and the sum stays exact."""
    sim = _sim(policy="vllm")
    sim.run(_reqs(n=16, prompt=384, output=48, rate=16.0))
    tr = sim.core.tracer
    _assert_exact(sim.done, tr)
    causes = {c for b in tr.breakdowns().values() for c in b}
    assert "gate:device_blocks" in causes
    gates = [e for e in tr.events if e["type"] == "sched_pass"
             and e["args"]["stop_gate"] == "gate:device_blocks"]
    assert gates, "no pass recorded the device gate as its stop reason"


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["exclusive", "chunked"])
def test_engine_ttft_decomposition_exact(chunked):
    """Same contract on the real engine (including the exclusive
    prefill-inside-admission path). The tracer keeps one timeline, the
    virtual clock: the engine's wall-clock record is its profiler spans
    (tests/test_spans.py)."""
    import dataclasses
    import jax
    from repro.configs import get_smoke_config
    from repro.serving.engine import LayerKVEngine
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    ec = ServeConfig.for_engine(policy="layerkv", chunked=chunked,
                                num_device_blocks=64, trace=True)
    eng = LayerKVEngine(cfg, None, ec, rng=jax.random.PRNGKey(0))
    rng = random.Random(0)
    reqs, t = [], 0.0
    for i in range(6):
        t += rng.expovariate(20.0)
        reqs.append(Request(
            rid=f"r{i}", prompt_len=24, output_len=8, arrival=t,
            prompt=[rng.randrange(cfg.vocab_size) for _ in range(24)]))
    done = eng.run(reqs)
    tr = eng.core.tracer
    assert len(tr.breakdowns()) == 6
    _assert_exact(done, tr)
    assert not any("wall" in ev for ev in tr.events)
    assert not hasattr(tr, "wall_clock")
    # executor counters live on the core's registry (one namespace)
    assert eng.ex.registry is eng.core.registry
    assert sum(eng.ex.jit_retraces.values()) \
        == eng.core.registry.total("jit_retraces") > 0


def test_recompute_preemption_reopens_partition_exactly():
    """A vLLM recompute preemption resets first_token_time; the tracer
    reopens the partition (discarded decode time -> recompute_lost, the
    requeue wait -> recompute_requeue) and the invariant holds for the
    NEW first token."""
    class _Pool:
        num_blocks = 8

    class _Ledger:
        busy_until = 0.0
        log = ()

    class _Off:
        ledger = _Ledger()

    class _BM:
        tables = {}
        pools = {DEVICE: _Pool(), HOST: _Pool()}

        def num_free(self, pool):
            return 8

    class _Core:
        L = 2
        waiting = ()
        paused = ()
        bm = _BM()
        off = _Off()

        def in_flight(self):
            return 0

    tr = Tracer()
    r = Request(rid="x", prompt_len=16, output_len=8, arrival=0.0)
    r.prefill_start = 1.0
    tr.sched_pass(_Core(), 1.0, [r], None)           # queued 0..1
    r.first_token_time = 2.0
    tr.first_token(r, 2.0)                           # prefill 1..2
    assert sum(tr.ttft_breakdown("x").values()) == pytest.approx(2.0)
    r.first_token_time = -1.0                        # recompute reset
    r.n_preempted += 1
    tr.preempt(r, 5.0, mode="recompute")             # lost 2..5
    r.prefill_start = 7.0
    tr.sched_pass(_Core(), 7.0, [r], None)           # requeue 5..7
    r.first_token_time = 9.0
    tr.first_token(r, 9.0)                           # prefill 7..9
    b = tr.ttft_breakdown("x")
    assert b["recompute_lost"] == pytest.approx(3.0)
    assert b["recompute_requeue"] == pytest.approx(2.0)
    assert b["prefill"] == pytest.approx(3.0)
    assert sum(b.values()) == pytest.approx(9.0)     # == new ttft
    assert tr.breakdowns()["x"] == b                 # finalized again
    # two queued spans: the original wait and the requeue wait
    spans = [e for e in tr.events if e["type"] == "queued"]
    assert [(e["t0"], e["t1"]) for e in spans] == [(0.0, 1.0), (5.0, 7.0)]


# ----------------------------------------------------------- event battery ---

def test_every_event_type_is_emitted():
    """Union of events over reachable scenarios == EVENT_TYPES exactly:
    the documented vocabulary neither rots nor grows silently."""
    seen = set()

    def collect(*tracers):
        for tr in tracers:
            seen.update(ev["type"] for ev in tr.events)

    # lifecycle + chunked spans + mid-flight cancel
    sim = _sim(chunked=True)
    sess = ServingSession(sim)
    hs = [sess.submit(r) for r in _reqs(n=4)]   # all queued at t=0
    sess.step()
    sess.cancel(hs[-1])
    sess.drain()
    collect(sim.core.tracer)

    # lossless preemption: preempt / resume / paused
    simp = _sim(chunked=True, admission="deadline", preemption=True,
                num_device_blocks=160, block_size=16)
    reqs = [Request(rid=f"b{i}", prompt_len=400, output_len=300,
                    arrival=0.01 * i, priority=0,
                    ttft_slo=60.0, tpot_slo=10.0) for i in range(6)]
    reqs += [Request(rid=f"i{j}", prompt_len=400, output_len=40,
                     arrival=3.0 + 2 * j, priority=1,
                     ttft_slo=1.0, tpot_slo=0.5) for j in range(3)]
    simp.run(reqs)
    assert simp.core.n_preempted > 0
    collect(simp.core.tracer)

    # graceful degradation: an infeasible request is shed, not wedged
    sims = _sim(num_device_blocks=64, block_size=16, shed_overload=True)
    shed_sess = ServingSession(sims)
    shed_sess.submit(Request(rid="big", prompt_len=65536, output_len=4,
                             arrival=0.0), arrival=0.0)
    shed_sess.drain()
    assert sims.core.shed
    collect(sims.core.tracer)

    # cluster faults over a 1-replica fleet: the crash mid-burst kills
    # in-flight work, re-dispatch finds no live replica -> backoff
    # retries until the revive; manual drain_replica covers "drain"
    plan = FaultPlan.parse("crash@0.4:r0:recover=2.0", n_replicas=1)
    cl = ClusterSession([_sim(chunked=True)], fault_plan=plan)
    for r in multi_tenant(16, rate=16.0, n_tenants=2, prompt_len=256,
                          output_len=24, seed=7):
        cl.submit(r, arrival=r.arrival)
    cl.drain()
    assert cl.n_kills == 1 and cl.n_recoveries == 1
    assert cl.n_retries >= 1
    cl.drain_replica(0)
    collect(cl.tracer, *[s.core.tracer for s in cl.sessions])

    assert seen == set(EVENT_TYPES), \
        (sorted(set(EVENT_TYPES) - seen), sorted(seen - set(EVENT_TYPES)))


def test_sched_pass_decision_record_contents():
    """The per-pass decision record carries who/why plus pool occupancy
    per layer/tier and ledger activity."""
    sim = _sim(chunked=True)
    sim.run(_reqs(n=6))
    passes = [e for e in sim.core.tracer.events
              if e["type"] == "sched_pass"]
    assert passes
    gates = set(ATTRIBUTION_CAUSES) | {None}
    for p in passes:
        a = p["args"]
        assert set(a["blocked"].values()) <= set(ATTRIBUTION_CAUSES)
        assert a["stop_gate"] in gates
        for tier in (DEVICE, HOST):
            assert 0 <= a["pool"][tier]["free"] \
                <= a["pool"][tier]["total"]
        assert len(a["layer_device_blocks"]) == sim.core.L
        assert len(a["layer_host_blocks"]) == sim.core.L
        assert a["ledger"]["n_transfers"] >= 0
    admitted = {rid for p in passes for rid in p["args"]["admitted"]}
    assert admitted == {r.rid for r in sim.done}


# ------------------------------------------------------- off == identical ---

_ARMS = {
    "vllm-exclusive": dict(policy="vllm"),
    "layerkv-exclusive": dict(policy="layerkv"),
    "layerkv-chunked": dict(policy="layerkv", chunked=True),
    "layerkv-fused": dict(policy="layerkv", chunked=True, fused=True),
    "layerkv-prefix": dict(policy="layerkv", chunked=True,
                           prefix_cache=True),
    "layerkv-preempt": dict(policy="layerkv", chunked=True,
                            admission="deadline", preemption=True),
}


@pytest.mark.parametrize("arm", _ARMS, ids=list(_ARMS))
def test_trace_off_is_bit_identical(arm):
    """trace=True must OBSERVE, never steer: metrics (raw series
    included) are bit-identical with tracing on and off, on every
    scheduling arm."""
    def run(trace):
        sim = _sim(trace=trace, **_ARMS[arm])
        return sim.run(multi_tenant(14, rate=16.0, n_tenants=3,
                                    prompt_len=256, output_len=24,
                                    seed=3))
    assert run(True) == run(False)


def test_trace_off_never_imports_tracer():
    """Zero-overhead contract, checked in a pristine interpreter: a
    trace=False run never loads repro.obs.trace and installs no
    tracer."""
    code = """
import sys
from repro.configs.llama2_7b import CONFIG
from repro.serving.costmodel import L20
from repro.serving.sim import ServingSimulator
from repro.serving.scheduler import ServeConfig
from repro.serving.request import Request
sim = ServingSimulator(CONFIG, L20, ServeConfig.for_sim())
sim.run([Request(rid="r0", prompt_len=64, output_len=8, arrival=0.0)])
assert sim.core.tracer is None
assert "repro.obs.trace" not in sys.modules, "tracer imported when off"
assert "repro.obs.export" not in sys.modules, "exporter imported when off"
assert "repro.obs.registry" in sys.modules   # the always-on half
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd="src",
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "OK"


# --------------------------------------------------------------- exporters ---

def _check_chrome_trace(doc, want_pids=None):
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] != "M"}
    assert names <= set(EVENT_TYPES)
    last_ts = {}
    for e in evs:
        if e["ph"] == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last_ts.get(key, float("-inf")), \
            f"timestamps regressed on track {key}"
        last_ts[key] = e["ts"]
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["ph"] == "i" and e["s"] in ("t", "p")
    if want_pids is not None:
        assert {e["pid"] for e in evs} == want_pids


def test_session_write_trace_valid_chrome_json(tmp_path):
    sim = _sim(chunked=True)
    sess = ServingSession(sim)
    for r in _reqs(n=5):
        sess.submit(r, arrival=r.arrival)
    sess.drain()
    path = tmp_path / "trace.json"
    sess.write_trace(str(path))
    doc = json.loads(path.read_text())
    _check_chrome_trace(doc, want_pids={0})
    # one span track per request + the scheduler track
    tids = {e["tid"] for e in doc["traceEvents"]}
    assert len(tids) == 1 + 5


def test_write_trace_requires_tracing_on():
    sim = _sim(trace=False)
    with pytest.raises(ValueError, match="trac"):
        ServingSession(sim).write_trace("/dev/null")


def test_cluster_perfetto_merges_replicas_and_fleet_track(tmp_path):
    plan = FaultPlan.parse("crash@0.4:r0:recover=2.0", n_replicas=2)
    cl = ClusterSession([_sim(chunked=True) for _ in range(2)],
                        fault_plan=plan)
    for r in multi_tenant(16, rate=16.0, n_tenants=2, prompt_len=256,
                          output_len=24, seed=7):
        cl.submit(r, arrival=r.arrival)
    cl.drain()
    assert cl.n_kills == 1
    doc = cl.perfetto()
    _check_chrome_trace(doc, want_pids={0, 1, 2})  # 2 replicas + fleet
    labels = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert labels == {"replica 0", "replica 1", "cluster"}
    kills = [e for e in doc["traceEvents"] if e["name"] == "kill"]
    assert kills and kills[0]["pid"] == 2       # on the fleet track
    path = tmp_path / "cluster.json"
    cl.write_trace(str(path))
    assert json.loads(path.read_text()) == doc
    # the fleet snapshot pools per-replica registries under a label
    snap = cl.snapshot()
    assert snap["replica_kills"] == 1.0
    assert any("replica=" in k for k in snap)
    assert "replica_kills 1\n" in prometheus_text(snap)


def test_perfetto_skips_missing_tracers():
    doc = perfetto_trace([None, Tracer()], labels=["a", "b"])
    assert all(e["pid"] == 1 for e in doc["traceEvents"])


# -------------------------------------------------- per-tenant reporting ---

def test_class_report_by_tenant():
    """`SimMetrics.class_report(by="tenant")` re-keys the pooled raw
    series on the tenant id encoded in `t{k}r{i}` rids."""
    sim = _sim(chunked=True)
    m = sim.run(multi_tenant(18, rate=16.0, n_tenants=3, prompt_len=256,
                             output_len=24, seed=5))
    rep = m.class_report(by="tenant")
    assert set(rep) <= {0, 1, 2} and len(rep) >= 2
    assert sum(e["n"] for e in rep.values()) == m.n_requests
    for e in rep.values():
        assert e["n"] > 0 and e["mean_ttft"] > 0.0
        assert e["p99_ttft"] >= e["mean_ttft"] * 0.5
        assert e["goodput"] >= 0.0 and e["n_shed"] == 0
        assert "n_retries" not in e        # tracked per priority only
    # default axis unchanged (back-compat): priority classes
    by_prio = m.class_report()
    assert set(by_prio) == {0}
    assert "n_retries" in by_prio[0]
    with pytest.raises(ValueError, match="tenant"):
        m.class_report(by="bogus")


def test_class_report_tenant_pools_foreign_rids_under_minus_one():
    from repro.serving.sim import SimMetrics
    m = SimMetrics(ttft=[1.0, 2.0], queuing=[0.0, 0.0],
                   prefill_lat=[0.0, 0.0], tpot=[0.0, 0.0],
                   finish_times=[1.0, 2.0], tokens_out=4, makespan=2.0,
                   slo_violations=0, n_requests=2, preemptions=0,
                   priorities=[0, 0], tbt=[0.0, 0.0],
                   deadline_slack=[1.0, 1.0], req_tokens=[2, 2],
                   rids=["t1r0", "plain"])
    rep = m.class_report(by="tenant")
    assert set(rep) == {-1, 1}
    assert rep[1]["mean_ttft"] == pytest.approx(1.0)
    assert rep[-1]["mean_ttft"] == pytest.approx(2.0)
