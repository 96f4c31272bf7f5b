"""Pallas TPU paged GQA decode-attention kernel.

One query token per sequence attends over KV stored in a *single pooled
tensor* of fixed-size blocks (paper §4: one physical tensor, logical
per-layer allocation), addressed through a block table.

Pool layout is head-major, `(NB, 2, KV, BS, D)`: one grid step fetches
one KV head of one page as a `(1, 2, 1, BS, D)` block, whose last two
dims are the whole `(BS, D)` page slice — the shape Mosaic's tiling
rule accepts for any block size and head dim.

TPU adaptation of the CUDA PagedAttention kernel:
  * the block table (flattened to 1-D so SMEM holds it unpadded) and
    per-sequence lengths are **scalar-prefetched**
    (pltpu.PrefetchScalarGridSpec) so the BlockSpec index_map itself chases
    the page table — the DMA engine gathers KV blocks HBM->VMEM directly,
    there is no software gather;
  * grid = (B, KV_heads, n_blocks); the KV-block axis is the innermost
    sequential dimension, with online-softmax state in VMEM scratch
    (same structure as the prefill kernel);
  * all G = H/KV query heads of a KV group ride in one tile so the MXU sees
    a (G, D) x (D, BS) matmul per page instead of G vector products.

Compiled by Mosaic on a TPU; elsewhere callers must pass
`interpret=True` (Pallas refuses a compiled call off-TPU). Validated
against `ref.paged_attention_reference` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tab_ref, len_ref, q_ref, pool_ref, o_ref, m_sc, l_sc,
                  acc_sc, *, bs, n_blocks, scale):
    b = pl.program_id(0)
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    kv_len = len_ref[b]
    block_live = ib * bs < kv_len

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (G, D)
        k = pool_ref[0, 0, 0].astype(jnp.float32)       # (BS, D)
        v = pool_ref[0, 1, 0].astype(jnp.float32)       # (BS, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,BS)
        pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=1)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ib == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softmax_scale", "interpret"))
def paged_attention_pallas(q, kv_pool, block_table, kv_len, *,
                           softmax_scale=None, interpret=False):
    """q: (B, H, D); kv_pool: (NB, 2, KV, BS, D); block_table: (B, MAXB)
    int32; kv_len: (B,) int32. Returns (B, H, D)."""
    B, H, D = q.shape
    _, _, KV, BS, _ = kv_pool.shape
    MAXB = block_table.shape[1]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else D ** -0.5

    grid = (B, KV, MAXB)
    kernel = functools.partial(_paged_kernel, bs=BS, n_blocks=MAXB,
                               scale=scale)
    # q viewed as (B, KV, G, D) so one tile holds a KV group's query heads
    q4 = q.reshape(B, KV, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, kh, ib, tab, lens: (b, kh, 0, 0)),
            # page-table chase: physical block id comes from the prefetched
            # table; KV head rides in the block
            pl.BlockSpec((1, 2, 1, BS, D),
                         lambda b, kh, ib, tab, lens:
                             (tab[b * MAXB + ib], 0, kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kh, ib, tab, lens: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(block_table.reshape(-1), kv_len, q4, kv_pool)
    return out.reshape(B, H, D)
