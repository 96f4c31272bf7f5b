"""Pallas TPU flash-attention prefill kernel (causal / sliding-window, GQA).

TPU adaptation notes (vs. the usual CUDA flash kernel):
  * tiling is chosen for VMEM + MXU: q/k tiles default to 128 rows and the
    head dim rides along whole (128-aligned for every assigned arch except
    whisper/zamba2/granite, where 64/80 still maps onto the MXU with padding);
  * the KV loop is the innermost *sequential* grid dimension — on TPU the
    grid is executed in order, so the online-softmax state (m, l, acc) lives
    in VMEM scratch that persists across that dimension;
  * fully-masked KV tiles are skipped with @pl.when (causal upper triangle
    and out-of-window tiles), halving the causal FLOPs.

Off the served path: `ops.flash_attention` is the jnp implementation,
and this kernel's `(1, bq, 1, D)` blocks (a size-1 second-minor dim) are
refused by Mosaic's tiling rule, so it runs in interpret mode only
(`interpret=True`). Validated against `ref.flash_attention_reference`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  bq, bk, n_kv_blocks, causal, window, q_offset, scale):
    iq = pl.program_id(3)
    ik = pl.program_id(4)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * bq + q_offset
    k_start = ik * bk
    # tile-level skip: is any (i, j) pair in this tile live?
    live = jnp.array(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window:
        live &= k_start + bk - 1 > q_start - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale   # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=1)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "softmax_scale",
                     "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, kv_len=None,
                           q_offset=0, softmax_scale=None, block_q=128,
                           block_k=128, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D). kv_len unsupported here
    (engine prefills exact-length sequences); q_offset must be static."""
    assert kv_len is None, "pallas prefill kernel expects exact-length batches"
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk

    grid = (B, KV, G, nq, nk)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv_blocks=nk, causal=causal,
        window=window, q_offset=q_offset, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D),
                         lambda b, kh, g, iq, ik: (b, iq, kh * G + g, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, kh, g, iq, ik: (b, ik, kh, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, kh, g, iq, ik: (b, ik, kh, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D),
                               lambda b, kh, g, iq, ik: (b, iq, kh * G + g, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # m (online-softmax max)
            pltpu.VMEM((bq,), jnp.float32),      # l (normalizer)
            pltpu.VMEM((bq, D), jnp.float32),    # acc (output accumulator)
        ],
        interpret=interpret,
    )(q, k, v)
