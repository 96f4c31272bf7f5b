"""Kernel microbenchmarks: Pallas (interpret mode on the CPU) vs the jnp
oracle.

On a CPU the interesting output is CORRECTNESS + the HLO cost of the jnp
reference path; interpret-mode wall time says nothing about a TPU.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.kernels import ref
from repro.kernels.flash_prefill import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.paged_prefill import paged_prefill_pallas


def main(smoke: bool = False) -> None:
    key = jax.random.PRNGKey(0)
    # flash prefill
    B, S, H, KV, D = 1, (128 if smoke else 512), 8, 2, 128
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k = jax.random.normal(key, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(key, (B, S, KV, D), jnp.float32)
    ref_fn = jax.jit(lambda *a: ref.flash_attention_reference(*a))
    us_ref = timeit(lambda: ref_fn(q, k, v).block_until_ready())
    out_p = flash_attention_pallas(q, k, v, interpret=True)
    err = float(jnp.max(jnp.abs(out_p - ref_fn(q, k, v))))
    c = jax.jit(lambda *a: ref.mha_reference(*a)).lower(q, k, v).compile()
    cost = c.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax returns [dict] per device
        cost = cost[0] if cost else {}
    flops = (cost or {}).get("flops", 0.0)
    emit("kernel.flash_prefill", us_ref,
         f"maxerr_vs_pallas={err:.2e};hlo_flops={flops:.3g};"
         f"shape=B{B}xS{S}xH{H}xKV{KV}xD{D}")

    # paged decode attention
    B, H, KV, D, NB, BS, MAXB = 8, 8, 2, 128, 128, 16, (4 if smoke else 16)
    q1 = jax.random.normal(key, (B, H, D), jnp.float32)
    pool = jax.random.normal(key, (NB, 2, KV, BS, D), jnp.float32)
    tab = jax.random.permutation(key, NB)[:B * MAXB].reshape(B, MAXB)
    tab = tab.astype(jnp.int32)
    kv_len = jnp.full((B,), BS * MAXB - 3, jnp.int32)
    pref = jax.jit(lambda *a: ref.paged_attention_reference(*a))
    us_ref = timeit(lambda: pref(q1, pool, tab, kv_len).block_until_ready())
    outp = paged_attention_pallas(q1, pool, tab, kv_len, interpret=True)
    err = float(jnp.max(jnp.abs(outp - pref(q1, pool, tab, kv_len))))
    emit("kernel.paged_attention", us_ref,
         f"maxerr_vs_pallas={err:.2e};"
         f"shape=B{B}xH{H}xKV{KV}xD{D}xBS{BS}xMAXB{MAXB}")

    # paged chunk-prefill: one chunk attending straight against the pool
    # vs the legacy gather-to-dense + flash path it replaces
    TQ, C = 32, 32
    H, KV, D, BS = 8, 2, 128, 16
    MAXB = 8 if smoke else 32
    NB = MAXB + 16
    ctx = MAXB * BS - C - 5          # chunk ends 5 tokens shy of the table
    pool = jax.random.normal(key, (NB, 2, KV, BS, D), jnp.float32)
    tab = jax.random.permutation(key, NB)[:MAXB][None].astype(jnp.int32)
    Tc = -(-C // TQ) * TQ
    qc = jax.random.normal(key, (Tc, H, D), jnp.float32)
    seg = jnp.zeros(Tc, jnp.int32)
    qpos = ctx + jnp.arange(Tc, dtype=jnp.int32)
    klen = jnp.asarray([ctx + C], jnp.int32)
    ppref = jax.jit(lambda *a: ref.paged_prefill_reference(*a, tq=TQ))

    def gather_dense():
        g = pool[tab[0]].transpose(0, 3, 1, 2, 4)  # (MAXB, BS, 2, KV, D)
        k = g[:, :, 0].reshape(MAXB * BS, KV, D)[None]
        v = g[:, :, 1].reshape(MAXB * BS, KV, D)[None]
        return ref.flash_attention_reference(
            qc[None, :C], k, v, causal=True,
            kv_len=jnp.asarray([ctx + C]), q_offset=ctx)
    gd = jax.jit(gather_dense)
    us_gd = timeit(lambda: gd().block_until_ready())
    us_pp = timeit(
        lambda: ppref(qc, pool, tab, seg, qpos, klen).block_until_ready())
    outp = paged_prefill_pallas(qc, pool, tab, seg, qpos, klen, tq=TQ,
                                interpret=True)
    err = float(jnp.max(jnp.abs(
        outp - ppref(qc, pool, tab, seg, qpos, klen))))
    emit("kernel.paged_prefill", us_pp,
         f"gather_dense_us={us_gd:.1f};maxerr_vs_pallas={err:.2e};"
         f"shape=C{C}xH{H}xKV{KV}xD{D}xBS{BS}xMAXB{MAXB}")

    # fused mixed step (one forward: chunk + decode batch) vs the two-call
    # executor baseline it replaces
    from repro.configs import get_smoke_config
    from repro.serving.executor import (MixedChunk, MixedDecode,
                                        PagedExecutor)
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              dtype="float32")
    BS, ctx, C, R = 16, (64 if smoke else 256), 24, 4
    L = cfg.n_layers
    nb = -(-(ctx + C) // BS)
    ex = PagedExecutor(cfg, None, L * nb * (R + 1) + 8, 16, BS,
                       rng=jax.random.PRNGKey(0))
    nxt = 0
    ctabs, dtabs = [], []
    for _ in range(L):
        ctabs.append(list(range(nxt, nxt + nb)))
        nxt += nb
    for _ in range(R):
        t = []
        for _ in range(L):
            t.append(list(range(nxt, nxt + nb)))
            nxt += nb
        dtabs.append(t)
    rng = np.random.RandomState(0)
    ctoks = [int(x) for x in rng.randint(0, cfg.vocab_size, C)]
    dtoks = [int(x) for x in rng.randint(0, cfg.vocab_size, R)]
    ks, vs = zip(*(ex.gather_layer("device", ctabs[l], kv_valid=ctx)
                   for l in range(L)))
    kbuf, vbuf = jnp.stack(ks), jnp.stack(vs)
    tables = np.zeros((L, R, nb), np.int32)
    for r in range(R):
        for l in range(L):
            tables[l, r] = dtabs[r][l]

    def two_call():
        logits, kc, vc = ex.prefill_chunk(ctoks, ctx, kbuf, vbuf)
        for l in range(L):
            ex.write_layer_slice("device", ctabs[l], ctx, kc[l], vc[l])
        ex.decode(dtoks, tables, [ctx] * R)
        logits.block_until_ready()

    def fused():
        ex.mixed_step(
            [MixedChunk(tokens=ctoks, offset=ctx, tables=ctabs,
                        tiers=[False] * L)],
            [MixedDecode(token=dtoks[r], ctx=ctx, tables=dtabs[r])
             for r in range(R)])
    us_two = timeit(two_call)
    us_fused = timeit(fused)
    emit("kernel.fused_mixed_step", us_fused,
         f"two_call_us={us_two:.1f};speedup={us_two / us_fused:.2f}x;"
         f"ctx{ctx}xC{C}xR{R}")


if __name__ == "__main__":
    main()
