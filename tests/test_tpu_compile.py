"""Ahead-of-time compiles of the served Pallas kernels for a described
TPU v5e — no chip needed, no kernel run. Each compile must lower to a
Mosaic `tpu_custom_call`, which is what the chip's compiler refuses for
blocks its tiling rule rejects (interpret mode never checks that).

Widths: granite-3-2b (H=32, KV=8, D=64) at block sizes 8 and 16, and
llama2-7b (H=KV=32, D=128), all bf16. Table widths are at least those
the chip smoke run serves with. Two whole executor steps (fused mixed
step, paged decode) compile with the kernels inside them, and the
decode step's optimized HLO is checked for pool relayouts.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs these tests loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kv_write import paged_kv_write_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.paged_prefill import paged_prefill_pallas

WIDTHS = {  # name: (H, KV, D, BS)
    "granite-bs8": (32, 8, 64, 8),
    "granite-bs16": (32, 8, 64, 16),
    "llama2-7b-bs16": (32, 32, 128, 16),
}
NB, NBH = 8193, 4097    # device / host pool pages (incl. trash block)
B, MAXB = 8, 32          # decode rows x table width
T, S, TQ = 128, 4, 32    # fused-step chunk rows, segments, query tile


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _args(kernel, width, sharding):
    H, KV, D, BS = WIDTHS[width]
    bf, i32 = jnp.bfloat16, jnp.int32
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    pool = s((NB, 2, KV, BS, D), bf)
    if kernel == "paged_attention":
        fn = lambda q, p, tab, lens: paged_attention_pallas(q, p, tab, lens)
        return fn, (s((B, H, D), bf), pool, s((B, MAXB), i32), s((B,), i32))
    if kernel == "paged_kv_write":
        return paged_kv_write_pallas, (pool, s((B,), i32), s((B,), i32),
                                       s((B, KV, D), bf), s((B, KV, D), bf))
    args = (s((T, H, D), bf), pool, s((S, MAXB), i32), s((T,), i32),
            s((T,), i32), s((S,), i32))
    if kernel == "paged_prefill":
        fn = lambda *a: paged_prefill_pallas(*a, tq=TQ)
        return fn, args
    fn = lambda q, p, tab, seg, pos, lens, hp, tier: paged_prefill_pallas(
        q, p, tab, seg, pos, lens, host_pool=hp, tier=tier, tq=TQ)
    return fn, args + (s((NBH, 2, KV, BS, D), bf), s((S,), jnp.bool_))


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["paged_attention", "paged_prefill",
                                    "paged_prefill_tiered", "paged_kv_write"])
def test_served_kernel_compiles_for_v5e(kernel, width, one_chip,
                                        no_compile_cache):
    fn, args = _args(kernel, width, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_step(one_chip, monkeypatch, BS):
    """granite-3-2b at full width, 2 layers deep, as shapes on the
    described chip: (executor, params, shape maker, pool shape)."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serving.executor import PagedExecutor
    # the described chip is not the default backend: steer ops by hand
    monkeypatch.setattr(ops, "paged_impl", lambda: "pallas")
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)))
    ex = PagedExecutor(cfg, params, 1, 1, BS)   # shapes only; no arrays
    return ex, params, s, (NB, 2, cfg.n_kv_heads, BS, cfg.resolved_head_dim)


def _lower_decode(ex, params, s, pool_shape):
    L = ex.cfg.n_layers
    return jax.jit(ex._paged_decode, donate_argnames=("dpool",)).lower(
        params, s((B,)), s((L, B, MAXB)), s((B,)),
        s(pool_shape, jnp.bfloat16))


@pytest.mark.parametrize("step", ["mixed", "decode"])
def test_served_step_compiles_for_v5e(step, one_chip, no_compile_cache,
                                      monkeypatch):
    """One jitted executor step of granite-3-2b at full width (2 layers
    deep) with the Pallas paged kernels in it: the fused mixed step with
    host-tier chunk segments, and the exclusive-mode paged decode."""
    ex, params, s, pool_shape = _granite_step(one_chip, monkeypatch, BS=16)
    L, KV, hd = ex.cfg.n_layers, ex.cfg.n_kv_heads, pool_shape[-1]
    dpool = s(pool_shape, jnp.bfloat16)
    if step == "decode":
        lowered = _lower_decode(ex, params, s, pool_shape)
    else:
        Tc, Sc, Rb, Sb = 64, 2, 4, 8
        T = Tc + Rb
        lowered = type(ex)._mixed_forward.lower(
            ex, params, s((T,)), s((T,)), s((T,)), s((L, T)), s((L, T)),
            s((Tc,)), s((Tc,)), s((Sc,)), s((L, Sc, MAXB)),
            s((L, Sc), jnp.bool_), s((L, Rb, MAXB)), s((Rb,)), s((Sb,)),
            s((Sb,), jnp.bool_), dpool,
            s((NBH, 2, KV, pool_shape[3], hd), jnp.bfloat16), True)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_decode_step_writes_pool_in_place(one_chip, no_compile_cache,
                                          monkeypatch):
    """The decode step writes each layer's token KV in place: its
    optimized HLO holds no scatter and at most the pool's entry and exit
    relayouts. With an XLA scatter the step holds 2 + L copies of the
    whole pool: the scatter's layout is not the paged kernel's."""
    ex, params, s, pool_shape = _granite_step(one_chip, monkeypatch, BS=128)
    hlo = _lower_decode(ex, params, s, pool_shape).compile().as_text()
    pool = "bf16[%s]" % ",".join(map(str, pool_shape))
    assert not re.search(r"\sscatter\(", hlo)
    copies = re.findall(re.escape(pool) + r"\S* copy\(", hlo)
    assert "paged_kv_write" in hlo and len(copies) <= 2, copies
