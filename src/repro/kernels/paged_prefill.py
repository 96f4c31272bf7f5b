"""Pallas TPU paged chunk-prefill attention kernel.

A prefill chunk attends **directly against the pooled KV tensor** — no
dense prefix gather (`gather_layer`), no `dynamic_update_slice` staging
buffer. This is the prefill-side twin of `paged_attention.py` and the
kernel behind the fused mixed step (one forward per serving iteration):

  * the flat token batch is a concatenation of per-request *segments*
    (a prefill chunk = its chunk tokens, a decode request = one token),
    each padded to the query tile `TQ` so a tile never straddles two
    segments;
  * grid = (KV_heads, n_q_tiles, MAXB): query-tile x block-table-chase.
    The per-segment block table, tile->segment map, tile base positions
    and per-segment KV lengths are **scalar-prefetched**
    (pltpu.PrefetchScalarGridSpec) so the BlockSpec index_map itself
    chases the page table — the DMA engine gathers KV blocks HBM->VMEM;
  * the KV-block axis is the innermost sequential dimension with
    online-softmax state in VMEM scratch; causal masking of the
    in-chunk tail runs against absolute positions (`q_offset` per tile
    base), so already-cached prefix KV and the chunk's freshly scattered
    KV are handled by one mask;
  * fully-masked tiles (causal upper triangle past the chunk, blocks
    beyond kv_len) are skipped with @pl.when;
  * all G = H/KV query heads of a KV group ride in the tile as a
    (TQ*G, D) x (D, BS) MXU matmul per page.

With `tier`/`host_pool` set (layer-wise offload mid-prefill: a segment's
blocks live in the HOST pool), both pools' candidate blocks are fetched
and the live one selected in-kernel. That costs 2x KV DMA for the
host-resident variant — acceptable because mid-prefill host residency is
the exception; a production TPU deployment would pin the host tier in
device-mappable memory or pre-stage, which this repo models at the
block-manager level.

Layouts Mosaic tiles cleanly: the pool is head-major `(NB, 2, KV, BS,
D)` (a grid step fetches one head of one page, a whole `(BS, D)` slice),
block tables ride SMEM flattened to 1-D, and q/o are laid out
`(KV, n_q_tiles, TQ*G, D)` by the wrapper so a tile is already the 2-D
`(TQ*G, D)` matmul operand — no in-kernel reshape.

Compiled by Mosaic on a TPU; elsewhere callers must pass
`interpret=True` (Pallas refuses a compiled call off-TPU). Validated
against `ref.paged_prefill_reference` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_body(q_ref, m_sc, l_sc, acc_sc, k, v, *, ib, bs, g, scale, q0,
                  kv_len):
    """Shared online-softmax update for one (q_tile, kv_block) pair.
    k/v: (BS, D) f32 already selected from the right pool. `ib` is passed
    in: pl.program_id is read once at kernel top level (this jax version
    cannot lower it inside a pl.when body in interpret mode)."""
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (tq*g, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (tq*g, BS)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
    q_abs = q0 + row
    k_pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (q_abs >= k_pos) & (k_pos < kv_len)
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


def _init_finalize(o_ref, m_sc, l_sc, acc_sc, *, ib):
    @pl.when(ib == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)
    return _finalize


def _paged_prefill_kernel(tab_ref, tseg_ref, tqpos_ref, len_ref, q_ref,
                          pool_ref, o_ref, m_sc, l_sc, acc_sc, *, bs, g, tq,
                          n_blocks, scale):
    it, ib = pl.program_id(1), pl.program_id(2)
    finalize = _init_finalize(o_ref, m_sc, l_sc, acc_sc, ib=ib)
    seg = tseg_ref[it]
    kv_len = len_ref[seg]
    q0 = tqpos_ref[it]
    live = (ib * bs < kv_len) & (ib * bs <= q0 + tq - 1)

    @pl.when(live)
    def _compute():
        k = pool_ref[0, 0, 0].astype(jnp.float32)         # (BS, D)
        v = pool_ref[0, 1, 0].astype(jnp.float32)
        _prefill_body(q_ref, m_sc, l_sc, acc_sc, k, v, ib=ib, bs=bs, g=g,
                      scale=scale, q0=q0, kv_len=kv_len)

    pl.when(ib == n_blocks - 1)(finalize)


def _paged_prefill_kernel_tiered(tab_ref, tier_ref, tseg_ref, tqpos_ref,
                                 len_ref, q_ref, dpool_ref, hpool_ref, o_ref,
                                 m_sc, l_sc, acc_sc, *, bs, g, tq, n_blocks,
                                 scale):
    """Two-pool variant: a segment whose layer was offloaded mid-prefill
    reads its blocks from the HOST pool (tier flag), everything else from
    the device pool. Both candidate blocks ride the tile (2x KV DMA)."""
    it, ib = pl.program_id(1), pl.program_id(2)
    finalize = _init_finalize(o_ref, m_sc, l_sc, acc_sc, ib=ib)
    seg = tseg_ref[it]
    kv_len = len_ref[seg]
    q0 = tqpos_ref[it]
    is_host = tier_ref[seg] != 0
    live = (ib * bs < kv_len) & (ib * bs <= q0 + tq - 1)

    @pl.when(live)
    def _compute():
        kd = dpool_ref[0, 0, 0].astype(jnp.float32)
        vd = dpool_ref[0, 1, 0].astype(jnp.float32)
        kh = hpool_ref[0, 0, 0].astype(jnp.float32)
        vh = hpool_ref[0, 1, 0].astype(jnp.float32)
        k = jnp.where(is_host, kh, kd)
        v = jnp.where(is_host, vh, vd)
        _prefill_body(q_ref, m_sc, l_sc, acc_sc, k, v, ib=ib, bs=bs, g=g,
                      scale=scale, q0=q0, kv_len=kv_len)

    pl.when(ib == n_blocks - 1)(finalize)


@functools.partial(jax.jit, static_argnames=("tq", "softmax_scale",
                                             "interpret"))
def paged_prefill_pallas(q, kv_pool, block_table, seg_ids, q_pos, kv_len, *,
                         host_pool=None, tier=None, tq=8, softmax_scale=None,
                         interpret=False):
    """q: (T, H, D) flat segment-padded token batch, T % tq == 0, with each
    tq-row tile entirely inside one segment; kv_pool: (NB, 2, KV, BS, D);
    block_table: (S, MAXB) int32; seg_ids/q_pos: (T,) int32; kv_len: (S,)
    int32. Optional host_pool (NBH, 2, KV, BS, D) + tier (S,) selects the
    pool per segment. Returns (T, H, D).

    The caller guarantees the chunk's own KV is already scattered into the
    pool — the kernel reads prefix AND in-chunk keys through the table,
    with the causal mask (q_pos >= k_pos) handling the in-chunk tail."""
    T, H, D = q.shape
    KV, BS = kv_pool.shape[2], kv_pool.shape[3]
    MAXB = block_table.shape[1]
    G = H // KV
    NT = T // tq
    scale = softmax_scale if softmax_scale is not None else D ** -0.5

    # (KV, NT, tq*G, D): row r of a tile is token r // G, head r % G
    qt = q.reshape(NT, tq, KV, G, D).transpose(2, 0, 1, 3, 4) \
        .reshape(KV, NT, tq * G, D)
    tab = block_table.reshape(-1)
    tile_seg = seg_ids.reshape(NT, tq)[:, 0].astype(jnp.int32)
    tile_qpos = q_pos.reshape(NT, tq)[:, 0].astype(jnp.int32)
    grid = (KV, NT, MAXB)
    scratch = [
        pltpu.VMEM((tq * G,), jnp.float32),
        pltpu.VMEM((tq * G,), jnp.float32),
        pltpu.VMEM((tq * G, D), jnp.float32),
    ]
    q_spec = pl.BlockSpec(
        (1, 1, tq * G, D), lambda kh, it, ib, *pf: (kh, it, 0, 0))
    out_spec = pl.BlockSpec(
        (1, 1, tq * G, D), lambda kh, it, ib, *pf: (kh, it, 0, 0))
    out_shape = jax.ShapeDtypeStruct(qt.shape, q.dtype)

    if tier is None:
        kernel = functools.partial(_paged_prefill_kernel, bs=BS, g=G, tq=tq,
                                   n_blocks=MAXB, scale=scale)
        pool_spec = pl.BlockSpec(
            (1, 2, 1, BS, D),
            lambda kh, it, ib, tab, tseg, tqp, lens:
                (tab[tseg[it] * MAXB + ib], 0, kh, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=[q_spec, pool_spec], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(tab, tile_seg, tile_qpos, kv_len, qt, kv_pool)
    else:
        kernel = functools.partial(_paged_prefill_kernel_tiered, bs=BS, g=G,
                                   tq=tq, n_blocks=MAXB, scale=scale)
        # a host-resident segment's ids index the HOST pool (and vice
        # versa) — clamp the not-applicable fetch into range; the kernel's
        # `where` discards it
        nbd, nbh = kv_pool.shape[0], host_pool.shape[0]
        dpool_spec = pl.BlockSpec(
            (1, 2, 1, BS, D),
            lambda kh, it, ib, tab, tier_, tseg, tqp, lens:
                (jnp.minimum(tab[tseg[it] * MAXB + ib], nbd - 1), 0, kh, 0, 0))
        hpool_spec = pl.BlockSpec(
            (1, 2, 1, BS, D),
            lambda kh, it, ib, tab, tier_, tseg, tqp, lens:
                (jnp.minimum(tab[tseg[it] * MAXB + ib], nbh - 1), 0, kh, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=grid,
            in_specs=[q_spec, dpool_spec, hpool_spec], out_specs=out_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(tab, tier.astype(jnp.int32), tile_seg, tile_qpos, kv_len,
          qt, kv_pool, host_pool)
    return out.reshape(KV, NT, tq, G, D).transpose(1, 2, 0, 3, 4) \
        .reshape(T, H, D)
