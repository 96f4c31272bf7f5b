"""Fused paged mixed-step coverage (ISSUE 3).

Three layers of guarantees:
  * kernel parity — `paged_prefill_pallas` (interpret mode) matches
    `ref.paged_prefill_reference` across q_offset/kv_len edge cases
    (chunk straddling a block boundary, single-token final chunk,
    decode-style one-token segments, dummy zero-length segments, the
    two-pool host-tier variant), and the reference itself matches the
    dense gather+flash oracle and the paged decode oracle to float32
    reassociation;
  * engine losslessness — `EngineConfig.fused` (one forward per
    iteration, chunks attending straight against the pools) generates
    tokens identical to the two-call chunked engine: dense + MoE, tight
    pools forcing mid-prefill offload (host-tier segments in the fused
    step), and prefix-cache hits starting at prefill_done = cached_len;
  * bucketed-shape contract — power-of-two padded jit signatures
    (prefill pad_to, decode batch width, mixed T/S/MAXB) and the
    retrace counter; `gather_layer(kv_valid=...)` slicing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.llama2_7b import CONFIG as LLAMA2_7B
from repro.kernels import ops, ref
from repro.kernels.paged_prefill import paged_prefill_pallas
from repro.serving.costmodel import L20, CostModel
from repro.serving.engine import EngineConfig, LayerKVEngine
from repro.serving.executor import PagedExecutor, _bucket
from repro.serving.request import Request

TQ = 8


# ------------------------------------------------------------ kernel parity

def _pool(nb, bs, kv, d, seed=0):
    """Head-major pool (nb, 2, kv, bs, d), as the executor lays it out."""
    return jax.random.normal(jax.random.PRNGKey(seed), (nb, 2, kv, bs, d),
                             jnp.float32)


def _segments(specs, h, d, bs, maxb, nb, seed=1):
    """Build a flat TQ-padded batch from (q_offset, n_q_tokens) specs.
    Returns (q, tab, seg_ids, q_pos, kv_len)."""
    rng = np.random.RandomState(seed)
    pads = [-(-max(n, 1) // TQ) * TQ for _, n in specs]
    T = sum(pads)
    seg_ids = np.zeros(T, np.int32)
    q_pos = np.zeros(T, np.int32)
    kv_len = np.zeros(len(specs), np.int32)
    t = 0
    for i, ((off, n), pad) in enumerate(zip(specs, pads)):
        seg_ids[t:t + pad] = i
        q_pos[t:t + pad] = off + np.arange(pad)
        kv_len[i] = off + n
        t += pad
    tab = rng.permutation(nb)[:len(specs) * maxb].reshape(len(specs), maxb)
    q = jax.random.normal(jax.random.PRNGKey(seed + 7), (T, h, d),
                          jnp.float32)
    return (q, jnp.asarray(tab, jnp.int32), jnp.asarray(seg_ids),
            jnp.asarray(q_pos), jnp.asarray(kv_len))


@pytest.mark.parametrize("spec", [
    (13, 11),   # chunk straddling a block boundary (BS=8)
    (0, 16),    # first chunk of a fresh prompt, block-aligned
    (23, 1),    # single-token final chunk
    (5, 3),     # mid-block start AND end
])
def test_paged_prefill_pallas_matches_ref_edges(spec):
    H, KV, D, BS, NB, MAXB = 6, 2, 64, 8, 32, 4
    pool = _pool(NB, BS, KV, D)
    q, tab, seg, pos, klen = _segments([spec], H, D, BS, MAXB, NB)
    out = paged_prefill_pallas(q, pool, tab, seg, pos, klen, interpret=True)
    expect = ref.paged_prefill_reference(q, pool, tab, seg, pos, klen)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_paged_prefill_multi_segment_chunk_decode_dummy():
    """One kernel call serving a chunk, two decode tokens, and a padded
    dummy segment (kv_len 0) — the fused step's steady-state layout."""
    H, KV, D, BS, NB, MAXB = 8, 2, 32, 8, 48, 5
    pool = _pool(NB, BS, KV, D)
    specs = [(9, 12), (30, 1), (17, 1), (0, 0)]
    q, tab, seg, pos, klen = _segments(specs, H, D, BS, MAXB, NB)
    out = paged_prefill_pallas(q, pool, tab, seg, pos, klen, interpret=True)
    expect = ref.paged_prefill_reference(q, pool, tab, seg, pos, klen)
    # a fully-masked row (the kv_len=0 dummy segment) is garbage by
    # contract — callers discard it; compare live segments only and just
    # require the dummy rows to be finite
    live = np.asarray(klen)[np.asarray(seg)] > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(expect)[live],
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.isfinite(np.asarray(out)))


def test_paged_prefill_host_tier_variant():
    """Two-pool variant: host-resident segments read the HOST pool, with
    ids valid only there (the device-side fetch clamps and is discarded)."""
    H, KV, D, BS, MAXB = 4, 1, 32, 8, 3
    dpool = _pool(8, BS, KV, D, seed=3)       # small device pool
    hpool = _pool(64, BS, KV, D, seed=4)      # bigger host pool
    specs = [(4, 9), (11, 5)]
    q, _, seg, pos, klen = _segments(specs, H, D, BS, MAXB, 8)
    tab = jnp.asarray([[60, 33, 51], [2, 5, 1]], jnp.int32)  # host ids > NBd
    tier = jnp.asarray([True, False])
    out = paged_prefill_pallas(q, dpool, tab, seg, pos, klen,
                               host_pool=hpool, tier=tier, interpret=True)
    expect = ref.paged_prefill_reference(q, dpool, tab, seg, pos, klen,
                                         host_pool=hpool, tier=tier)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_paged_prefill_ref_matches_dense_flash_oracle():
    """The reference kernel == gather-to-dense + masked attention oracle
    (the two-call path's math) for a chunk at a q_offset."""
    H, KV, D, BS, NB, MAXB = 6, 3, 32, 8, 24, 3
    pool = _pool(NB, BS, KV, D)
    off, C = 10, 9
    q, tab, seg, pos, klen = _segments([(off, C)], H, D, BS, MAXB, NB)
    out = ref.paged_prefill_reference(q, pool, tab, seg, pos, klen)
    dense = pool[tab[0]].transpose(0, 3, 1, 2, 4)   # (MAXB, BS, 2, KV, D)
    k = dense[:, :, 0].reshape(MAXB * BS, KV, D)[None]
    v = dense[:, :, 1].reshape(MAXB * BS, KV, D)[None]
    expect = ref.mha_reference(q[None, :C], k, v, causal=True,
                               kv_len=jnp.array([off + C]), q_offset=off)
    # two jnp orderings of the same float32 math: XLA may reassociate
    # their reductions differently (observed gap <= 5.4e-7 abs); a bf16
    # path would miss this bound by orders of magnitude
    np.testing.assert_allclose(np.asarray(out[:C]), np.asarray(expect[0]),
                               atol=2e-6, rtol=1e-5)


def test_paged_prefill_ref_decode_row_matches_paged_attention():
    """A one-token segment (decode riding the fused step) == the decode
    oracle `paged_attention_reference`, to float32 reassociation."""
    H, KV, D, BS, NB, MAXB = 8, 2, 64, 16, 32, 4
    pool = _pool(NB, BS, KV, D)
    ctx = 41
    q, tab, seg, pos, klen = _segments([(ctx, 1)], H, D, BS, MAXB, NB)
    out = ref.paged_prefill_reference(q, pool, tab, seg, pos, klen)
    expect = ref.paged_attention_reference(
        q[:1], pool, tab, jnp.asarray([ctx + 1], jnp.int32))
    # same float32 math in two jnp orderings (see the oracle test above)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expect[0]),
                               atol=2e-6, rtol=1e-5)


def test_ops_paged_prefill_backend_dispatch():
    """Off-TPU `ops` runs the jnp oracle (exactly), which the Pallas
    kernel in interpret mode matches."""
    H, KV, D, BS, NB, MAXB = 4, 2, 32, 8, 16, 2
    pool = _pool(NB, BS, KV, D)
    q, tab, seg, pos, klen = _segments([(3, 5)], H, D, BS, MAXB, NB)
    assert ops.paged_impl() == "ref"
    before = ops.dispatched["paged_prefill", "ref"]
    a = ops.paged_prefill(q, pool, tab, seg, pos, klen)
    assert ops.dispatched["paged_prefill", "ref"] == before + 1
    np.testing.assert_array_equal(
        np.asarray(a),
        np.asarray(ref.paged_prefill_reference(q, pool, tab, seg, pos, klen)))
    b = paged_prefill_pallas(q, pool, tab, seg, pos, klen, interpret=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# --------------------------------------------------- bucketing / satellites

def test_bucket_power_of_two():
    assert [_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert _bucket(3, lo=8) == 8


def _tiny_executor(ndb=16, nhb=32, bs=8):
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    return PagedExecutor(cfg, None, ndb, nhb, bs,
                         rng=jax.random.PRNGKey(0)), cfg


def test_trash_block_is_extra_physical_block():
    ex, _ = _tiny_executor(ndb=16, nhb=32)
    assert ex.device_pool.shape[0] == 17
    assert ex.host_pool.shape[0] == 33


def test_decode_bucketing_counts_retraces_once_per_bucket():
    ex, cfg = _tiny_executor()
    L = cfg.n_layers
    # seed two sequences' KV via real prefills so decode reads valid blocks
    k = np.zeros((L, 3, 2), np.int32)  # 3 rows x 2 blocks of table space
    for r, blocks in enumerate(([0, 1], [2, 3], [4, 5])):
        _, kk, vv = ex.prefill([7, 3, 5, 2, 9][: 5], 16)
        for l in range(L):
            ex.write_layer("device", blocks, kk[l], vv[l])
        k[:, r, :] = blocks
    # R=2 and R=3 share the R-bucket 2->2? no: bucket(2)=2, bucket(3)=4
    out2 = ex.decode([1, 2], k[:, :2], [5, 5])
    out3 = ex.decode([1, 2, 3], k, [5, 5, 5])
    out3b = ex.decode([3, 2, 1], k, [5, 5, 5])
    assert len(out2) == 2 and len(out3) == 3 and len(out3b) == 3
    assert ex.jit_retraces["decode"] == 2  # buckets (2, ...) and (4, ...)
    # padded rows must not corrupt real rows: R=3 twice, same inputs
    assert ex.decode([1, 2, 3], k, [5, 5, 5]) == out3
    assert ex.jit_retraces["decode"] == 2  # still no new signature


def test_decode_rows_write_blocks_of_their_own():
    """The in-place KV write's contract, checked on the host table: rows
    on the trash block may share it (a warm-up decodes all rows there),
    two rows writing one real block may not."""
    ex, cfg = _tiny_executor()
    L, trash = cfg.n_layers, ex.num_device_blocks
    assert len(ex.decode([0] * 3, np.full((L, 3, 2), trash, np.int32),
                         [0] * 3)) == 3
    shared = np.array([[[0, 1], [2, 1]]] * L, np.int32)   # both on block 1
    with pytest.raises(AssertionError, match="share a current block"):
        ex.decode([1, 2], shared, [9, 9])


def test_prefill_pad_bucketing_shares_signatures():
    ex, _ = _tiny_executor()
    ex.prefill([1, 2, 3], 8)      # bucket 16
    ex.prefill([4, 5], 16)        # bucket 16 — same signature
    ex.prefill([1] * 20, 24)      # bucket 32
    assert ex.jit_retraces["prefill"] == 2


def test_gather_layer_kv_valid_slices_to_live_blocks():
    ex, _ = _tiny_executor()
    BS = ex.block_size
    _, k, v = ex.prefill(list(range(1, 21)), 24)
    ex.write_layer("device", [3, 6, 9], k[0], v[0])
    full_k, full_v = ex.gather_layer("device", [3, 6, 9])
    part_k, part_v = ex.gather_layer("device", [3, 6, 9], kv_valid=10)
    # live prefix identical, dead tail zeroed
    live = -(-10 // BS) * BS
    np.testing.assert_array_equal(np.asarray(part_k[:live]),
                                  np.asarray(full_k[:live]))
    assert np.all(np.asarray(part_k[live:]) == 0)
    assert np.all(np.asarray(part_v[live:]) == 0)
    zk, zv = ex.gather_layer("device", [3, 6, 9], kv_valid=0)
    assert zk.shape == full_k.shape and np.all(np.asarray(zk) == 0)
    assert np.all(np.asarray(zv) == 0)


def test_mixed_step_time_fused_arm():
    """The fused arm charges one weight stream: never slower than the
    two-call arm, strictly faster when the decode side was param-bound."""
    cm = CostModel(LLAMA2_7B, L20)
    t_chunk = cm.chunk_prefill_time(64, 512)
    for B, ctx in [(1, 128), (8, 512), (32, 2048)]:
        two = cm.mixed_step_time(t_chunk, B, ctx)
        fused = cm.mixed_step_time(t_chunk, B, ctx, fused=True)
        assert fused <= two + 1e-12
    # decode-bound iteration (tiny chunk): dropping the duplicated param
    # stream must strictly help
    t_small = cm.chunk_prefill_time(1, 0)
    assert cm.mixed_step_time(t_small, 8, 256, fused=True) \
        < cm.mixed_step_time(t_small, 8, 256)
    # no decode batch / no chunk: arms agree
    assert cm.mixed_step_time(t_chunk, 0, 0, fused=True) \
        == cm.mixed_step_time(t_chunk, 0, 0)
    assert cm.mixed_step_time(0.0, 4, 128, fused=True) \
        == cm.mixed_step_time(0.0, 4, 128)


def test_engine_fused_requires_chunked():
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    with pytest.raises(ValueError):
        LayerKVEngine(cfg, None, EngineConfig(fused=True, chunked=False))


# ------------------------------------------------------------- real engine

def _workload(cfg, n, plen_range, out_range, seed=0, arrivals=False):
    r0 = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(r0.randint(*plen_range))
        reqs.append(Request(
            rid=f"r{i}", prompt_len=plen,
            output_len=int(r0.randint(*out_range)),
            arrival=float(i) * 1e-6 if arrivals else 0.0,
            prompt=[int(x) for x in r0.randint(0, cfg.vocab_size, plen)]))
    return reqs


def _run_engine(cfg, reqs, ndb=40, fused=False, chunk_size=24,
                prefix_cache=False):
    eng = LayerKVEngine(
        cfg, None,
        EngineConfig(policy="layerkv", slo_aware=False,
                     num_device_blocks=ndb, num_host_blocks=512,
                     block_size=8, chunked=True, chunk_size=chunk_size,
                     fused=fused, prefix_cache=prefix_cache),
        rng=jax.random.PRNGKey(42))
    done = eng.run(reqs)
    return {r.rid: r.generated for r in done}, eng


@pytest.mark.slow
def test_engine_fused_lossless_dense():
    """THE fused guarantee: one forward per iteration (chunks attending
    straight against the pools) never changes generated tokens."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    mk = lambda: _workload(cfg, 4, (28, 52), (8, 14))
    out_two, _ = _run_engine(cfg, mk(), fused=False)
    out_f, eng = _run_engine(cfg, mk(), fused=True)
    assert max(r.n_chunks for r in eng.done) > 1, "workload must chunk"
    assert out_two == out_f
    # steady state reuses bucketed signatures: far fewer mixed traces
    # than iterations
    iters = sum(r.n_chunks + r.tokens_out for r in eng.done)
    assert 0 < eng.ex.jit_retraces["mixed"] < iters


@pytest.mark.slow
def test_engine_fused_lossless_moe():
    cfg = dataclasses.replace(get_smoke_config("deepseek-moe-16b"),
                              dtype="float32")
    mk = lambda: _workload(cfg, 3, (28, 48), (6, 12), seed=3)
    out_two, _ = _run_engine(cfg, mk(), fused=False)
    out_f, _ = _run_engine(cfg, mk(), fused=True)
    assert out_two == out_f


@pytest.mark.slow
def test_engine_fused_lossless_tight_pool_offload():
    """Tight pool forces layer-wise offload DURING chunked prefill: the
    fused step must read host-tier segments (two-pool kernel variant) and
    still match the two-call engine."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    mk = lambda: _workload(cfg, 5, (28, 52), (8, 16), seed=2)
    out_two, _ = _run_engine(cfg, mk(), ndb=30)
    out_f, eng = _run_engine(cfg, mk(), ndb=30, fused=True)
    n_off = len([t for t in eng.off.ledger.log if t.kind == "offload"])
    n_rel = len([t for t in eng.off.ledger.log if t.kind == "reload"])
    assert n_off > 0 and n_rel > 0, "pool must be tight enough to offload"
    assert any(sig[1][-1] for sig in eng.ex._jit_sigs
               if sig[0] == "mixed"), "host-tier fused step must run"
    assert out_two == out_f


@pytest.mark.slow
def test_engine_fused_lossless_prefix_cache_hits():
    """Prefix-cache hits start the fused chunk at prefill_done =
    cached_len: q_offset > 0 against shared blocks, tokens unchanged."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    r0 = np.random.RandomState(5)
    shared = [int(x) for x in r0.randint(0, cfg.vocab_size, 24)]

    def mk():
        reqs = []
        for i in range(4):
            tail = [int(x) for x in np.random.RandomState(100 + i)
                    .randint(0, cfg.vocab_size, 14)]
            p = shared + tail
            reqs.append(Request(rid=f"r{i}", prompt_len=len(p),
                                output_len=8, arrival=float(i) * 1e-6,
                                prompt=p))
        return reqs

    out_two, _ = _run_engine(cfg, mk(), ndb=64, chunk_size=16,
                             prefix_cache=True)
    out_f, eng = _run_engine(cfg, mk(), ndb=64, chunk_size=16,
                             prefix_cache=True, fused=True)
    assert any(r.cached_prompt_len > 0 for r in eng.done), \
        "workload must actually hit the cache"
    assert out_two == out_f


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Steer `ops` onto the Pallas kernels off-TPU, in interpret mode —
    the served path's kernel plumbing (pool layout, tables, tiers) as the
    chip runs it, minus the Mosaic compile."""
    import functools

    from repro.kernels import kv_write as kw
    from repro.kernels import paged_attention as pa
    from repro.kernels import paged_prefill as pp
    monkeypatch.setattr(ops, "paged_impl", lambda: "pallas")
    monkeypatch.setattr(pa, "paged_attention_pallas", functools.partial(
        pa.paged_attention_pallas, interpret=True))
    monkeypatch.setattr(pp, "paged_prefill_pallas", functools.partial(
        pp.paged_prefill_pallas, interpret=True))
    monkeypatch.setattr(kw, "paged_kv_write_pallas", functools.partial(
        kw.paged_kv_write_pallas, interpret=True))


@pytest.mark.slow
def test_engine_pallas_kernels_match_ref_engine(pallas_interpret):
    """The engine on the Pallas paged kernels (fused step with host-tier
    segments, and exclusive-mode paged decode) generates the ref
    engine's tokens."""
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    mk = lambda: _workload(cfg, 4, (28, 44), (6, 10), seed=2)
    before = dict(ops.dispatched)
    out_p, eng = _run_engine(cfg, mk(), ndb=30, fused=True)
    assert any(sig[1][-1] for sig in eng.ex._jit_sigs
               if sig[0] == "mixed"), "host-tier fused step must run"
    for op in ("paged_prefill", "paged_prefill_tiered", "paged_attention"):
        assert ops.dispatched[op, "pallas"] > before.get((op, "pallas"), 0)
    ex_p = LayerKVEngine(cfg, None, EngineConfig(
        policy="layerkv", slo_aware=False, num_device_blocks=30,
        num_host_blocks=512, block_size=8), rng=jax.random.PRNGKey(42))
    out_px = {r.rid: r.generated for r in ex_p.run(mk())}
    # the exclusive decode writes its token KV with the in-place kernel
    assert ops.dispatched["paged_kv_write", "pallas"] \
        > before.get(("paged_kv_write", "pallas"), 0)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "paged_impl", lambda: "ref")
        out_r, _ = _run_engine(cfg, mk(), ndb=30, fused=True)
        ex_r = LayerKVEngine(cfg, None, EngineConfig(
            policy="layerkv", slo_aware=False, num_device_blocks=30,
            num_host_blocks=512, block_size=8), rng=jax.random.PRNGKey(42))
        out_rx = {r.rid: r.generated for r in ex_r.run(mk())}
    assert out_p == out_r
    assert out_px == out_rx
