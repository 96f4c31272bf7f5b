"""Serving-layer tests: simulator behaviour (paper claims at small scale)
and real-engine losslessness under forced layer-wise offloading."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.llama2_7b import CONFIG as LLAMA2_7B
from repro.serving.costmodel import L20, CostModel
from repro.serving.engine import EngineConfig, LayerKVEngine
from repro.serving.request import Request
from repro.serving.sim import ServingSimulator, SimConfig
from repro.serving.workload import fixed_length, sharegpt_like


# ------------------------------------------------------------- simulator ---

def test_sim_queuing_dominates_at_long_context():
    """Paper Fig.1: beyond ~1k context, queuing >> prefill in TTFT."""
    reqs = fixed_length(80, 2048, 512, rate=1.0, seed=3)
    m = ServingSimulator(LLAMA2_7B, L20, SimConfig(policy="vllm")).run(reqs)
    assert m.mean_queuing > 5 * m.mean_prefill


def test_sim_layerkv_beats_vllm_ttft():
    """Paper Fig.4/6: LayerKV reduces mean TTFT by >=5x in the congested
    regime while keeping mean TPOT under the SLO."""
    r1 = fixed_length(80, 1024, 512, rate=1.0, seed=1)
    r2 = fixed_length(80, 1024, 512, rate=1.0, seed=1)
    mv = ServingSimulator(LLAMA2_7B, L20, SimConfig(policy="vllm")).run(r1)
    ml = ServingSimulator(LLAMA2_7B, L20,
                          SimConfig(policy="layerkv")).run(r2)
    assert ml.mean_ttft * 5 < mv.mean_ttft
    assert ml.mean_tpot < 0.25  # ~TPOT SLO (0.2s) with small tolerance


def test_sim_layerkv_lower_violation_rate():
    r1 = sharegpt_like(150, rate=4.0, seed=7)
    r2 = sharegpt_like(150, rate=4.0, seed=7)
    mv = ServingSimulator(LLAMA2_7B, L20, SimConfig(policy="vllm")).run(r1)
    ml = ServingSimulator(LLAMA2_7B, L20,
                          SimConfig(policy="layerkv")).run(r2)
    assert ml.violation_rate <= mv.violation_rate


def test_sim_slo_scheduler_protects_tpot():
    """Paper Fig.8 ablation: without the SLO-aware scheduler LayerKV's
    TPOT degrades vs. with it."""
    r1 = fixed_length(60, 2048, 384, rate=1.5, seed=5)
    r2 = fixed_length(60, 2048, 384, rate=1.5, seed=5)
    on = ServingSimulator(LLAMA2_7B, L20,
                          SimConfig(policy="layerkv", slo_aware=True)).run(r1)
    off = ServingSimulator(LLAMA2_7B, L20,
                           SimConfig(policy="layerkv",
                                     slo_aware=False)).run(r2)
    assert on.mean_tpot <= off.mean_tpot + 1e-6


def test_sim_block_accounting_clean():
    sim = ServingSimulator(LLAMA2_7B, L20, SimConfig(policy="layerkv"))
    sim.run(sharegpt_like(60, rate=3.0, seed=11))
    sim.bm.check()
    assert sim.bm.num_free("device") == sim.bm.pools["device"].num_blocks
    assert not sim.bm.live_requests()


# ------------------------------------------------------------ real engine --

def _workload(cfg, n, plen_range, out_range, seed=0):
    # simultaneous arrivals: queue pressure from step one (tiny smoke
    # models decode in virtual microseconds, so staggered arrivals would
    # serialize the requests and never stress the pool)
    r0 = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(r0.randint(*plen_range))
        reqs.append(Request(
            rid=f"r{i}", prompt_len=plen,
            output_len=int(r0.randint(*out_range)), arrival=0.0,
            prompt=[int(x) for x in r0.randint(0, cfg.vocab_size, plen)]))
    return reqs


def _run_engine(cfg, policy, ndb, reqs):
    # slo_aware off: admit as aggressively as blocks allow, so a tight pool
    # deterministically exercises the offload/reload machinery
    eng = LayerKVEngine(
        cfg, None,
        EngineConfig(policy=policy, slo_aware=False,
                     num_device_blocks=ndb,
                     num_host_blocks=512, block_size=8),
        rng=jax.random.PRNGKey(42))
    done = eng.run(reqs)
    return {r.rid: r.generated for r in done}, eng


@pytest.mark.slow
def test_engine_lossless_under_offload():
    """THE paper guarantee: layer-wise offloading never changes outputs.
    Tight device pool forces real offload+reload traffic."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    reqs_v = _workload(cfg, 8, (30, 60), (16, 30))
    reqs_l = _workload(cfg, 8, (30, 60), (16, 30))
    out_v, _ = _run_engine(cfg, "vllm", 1024, reqs_v)
    out_l, eng = _run_engine(cfg, "layerkv", 30, reqs_l)
    n_off = len([t for t in eng.off.ledger.log if t.kind == "offload"])
    n_rel = len([t for t in eng.off.ledger.log if t.kind == "reload"])
    assert n_off > 0 and n_rel > 0, "pool must be tight enough to offload"
    assert out_v == out_l


@pytest.mark.slow
def test_engine_lossless_moe():
    cfg = dataclasses.replace(get_smoke_config("deepseek-moe-16b"),
                              dtype="float32")
    reqs_v = _workload(cfg, 4, (24, 40), (8, 14), seed=3)
    reqs_l = _workload(cfg, 4, (24, 40), (8, 14), seed=3)
    out_v, _ = _run_engine(cfg, "vllm", 512, reqs_v)
    out_l, eng = _run_engine(cfg, "layerkv", 16, reqs_l)
    assert out_v == out_l


@pytest.mark.slow
def test_engine_layerkv_admits_earlier():
    """With a tight pool, layer-wise admission lets more requests begin
    prefill before any finishes (the paper's core mechanism)."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    cm = CostModel(cfg, L20)
    reqs_l = _workload(cfg, 6, (40, 41), (24, 25), seed=9)
    reqs_v = _workload(cfg, 6, (40, 41), (24, 25), seed=9)
    _, eng_l = _run_engine(cfg, "layerkv", 20, reqs_l)
    _, eng_v = _run_engine(cfg, "vllm", 20, reqs_v)
    ttft_l = np.mean([r.ttft for r in eng_l.done])
    ttft_v = np.mean([r.ttft for r in eng_v.done])
    assert ttft_l <= ttft_v + 1e-9


@pytest.mark.slow
def test_engine_partial_eviction_lossless():
    """A host pool too small to take a whole victim: eviction moves only
    some of its layers (17-block layers, 64 host blocks), the request
    must still be reloaded before it decodes, and tokens match the
    request-wise engine."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32", n_layers=4)
    r0 = np.random.RandomState(4)
    prompts = [[int(x) for x in r0.randint(0, cfg.vocab_size, 256)]
               for _ in range(8)]
    outs = []
    for policy, ndb, nhb in (("vllm", 2048, 64), ("layerkv", 256, 64)):
        eng = LayerKVEngine(
            cfg, None,
            EngineConfig(policy=policy, slo_aware=False,
                         num_device_blocks=ndb, num_host_blocks=nhb,
                         block_size=16),
            rng=jax.random.PRNGKey(42))
        reqs = [Request(rid=f"r{i}", prompt_len=256, output_len=12,
                        arrival=i * 1e-6, prompt=p)
                for i, p in enumerate(prompts)]
        outs.append({r.rid: r.generated for r in eng.run(reqs)})
    assert any(t.kind == "offload" for t in eng.off.ledger.log)
    assert outs[0] == outs[1]
