"""Pure-jnp oracles for every kernel in this package.

These are also the default lowering path at scale (the chunked flash oracle is
memory-O(S * chunk) and GSPMD-friendly), so they must be jit/scan-clean.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_logits(q, k):
    """q: (B, Sq, KV, G, D), k: (B, Skv, KV, D) -> (B, KV, G, Sq, Skv)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k)


def _gqa_out(p, v):
    """p: (B, KV, G, Sq, Skv), v: (B, Skv, KV, D) -> (B, Sq, KV, G, D)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def mha_reference(q, k, v, *, causal=True, window=0, kv_len=None, q_offset=0,
                  softmax_scale=None):
    """Unchunked masked GQA attention oracle.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D); H % KV == 0.
    kv_len: (B,) valid KV prefix length (None -> all valid).
    q_offset: absolute position of q[0] (int or (B,) array) for causal masking
      when Sq < Skv (decode / chunked prefill).
    window: >0 -> sliding-window attention (each query sees the last `window`
      keys, inclusive of itself).
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qh = (q * scale).reshape(B, Sq, KV, G, D)
    logits = _gqa_logits(qh, k).astype(jnp.float32)  # (B,KV,G,Sq,Skv)

    Skv = k.shape[1]
    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(Sq)  # (Sq,) or (B,Sq)
    k_pos = jnp.arange(Skv)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]  # (1, Sq)
    mask = jnp.ones((q_pos.shape[0], Sq, Skv), dtype=bool)
    if causal:
        mask &= q_pos[:, :, None] >= k_pos[None, None, :]
    if window:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    if kv_len is not None:
        mask &= k_pos[None, None, :] < jnp.asarray(kv_len).reshape(-1, 1, 1)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = _gqa_out(p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def flash_attention_reference(q, k, v, *, causal=True, window=0, kv_len=None,
                              q_offset=0, q_chunk=512, kv_chunk=512,
                              softmax_scale=None):
    """Chunked online-softmax GQA attention (flash oracle).

    Memory O(Sq/qc * Skv_chunk); numerically matches `mha_reference`.
    Shapes as in `mha_reference`. Sq % q_chunk == 0, Skv % kv_chunk == 0
    (callers pad); chunks larger than the dims are clamped.
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad ragged sequence lengths up to the chunk grid; padded KV positions
    # are masked via kv_len, padded q rows are sliced off the output
    orig_Sq, orig_Skv = Sq, Skv
    pad_q = (-Sq) % q_chunk
    pad_kv = (-Skv) % kv_chunk
    if pad_kv:
        kpad = [(0, 0), (0, pad_kv), (0, 0), (0, 0)]
        k = jnp.pad(k, kpad)
        v = jnp.pad(v, kpad)
        if kv_len is None:
            kv_len = jnp.full((B,), orig_Skv, jnp.int32)
        Skv += pad_kv
    if pad_q:
        q = jnp.pad(q, [(0, 0), (0, pad_q), (0, 0), (0, 0)])
        Sq += pad_q
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = softmax_scale if softmax_scale is not None else D ** -0.5

    # NB: keep q/k/v in their storage dtype here — f32 casts happen
    # per-chunk inside the scan bodies, otherwise a full-tensor f32 copy of
    # the activations lives across the whole attention call (at 32k prefill
    # that is GiBs per layer)
    qh = q.reshape(B, nq, q_chunk, KV, G, D)
    kh = k.reshape(B, nk, kv_chunk, KV, D)
    vh = v.reshape(B, nk, kv_chunk, KV, D)
    q_off = jnp.asarray(q_offset).reshape(-1, 1)  # (1or B,1)
    kv_len_arr = None if kv_len is None else jnp.asarray(kv_len).reshape(-1, 1, 1)

    def q_step(_, qi):
        qc = qh[:, qi].astype(jnp.float32) * scale  # (B, qc, KV, G, D)
        q_pos = q_off + qi * q_chunk + jnp.arange(q_chunk)  # (1orB, qc)

        def kv_step(carry, ki):
            m, l, acc = carry
            kc = kh[:, ki].astype(jnp.float32)
            vc = vh[:, ki].astype(jnp.float32)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            logits = _gqa_logits(qc, kc)  # (B,KV,G,qc,kc) f32
            mask = jnp.ones((q_pos.shape[0], q_chunk, kv_chunk), dtype=bool)
            if causal:
                mask &= q_pos[:, :, None] >= k_pos[None, None, :]
            if window:
                mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
            if kv_len_arr is not None:
                mask &= k_pos[None, None, :] < kv_len_arr
            logits = jnp.where(mask[:, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,qc,D)
        # stack in storage dtype: the f32 stack would be the biggest live
        # buffer of the whole prefill
        return None, out.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq,B,qc,KV,G,D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, D)
    if pad_q:
        out = out[:, :orig_Sq]
    return out.astype(q.dtype)


def decode_attention_reference(q, k_cache, v_cache, kv_len, *, window=0,
                               softmax_scale=None):
    """Single-token GQA decode attention over a dense cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S, KV, D); kv_len: (B,) number of
    valid entries. window: ring-buffer semantics are the caller's concern —
    here it only limits the attended span to the last `window` positions.
    """
    return mha_reference(
        q, k_cache, v_cache, causal=False, window=0,
        kv_len=kv_len, softmax_scale=softmax_scale,
    ) if window == 0 else mha_reference(
        # with a ring buffer every cached slot is within the window already
        q, k_cache, v_cache, causal=False, window=0, kv_len=kv_len,
        softmax_scale=softmax_scale,
    )


def _pages_to_seq(pages):
    """Gathered head-major pages (N, MAXB, 2, KV, BS, D) -> K and V as
    token-major sequences (N, MAXB*BS, KV, D)."""
    n, maxb, _, kv, bs, d = pages.shape
    seq = pages.transpose(0, 1, 4, 2, 3, 5).reshape(n, maxb * bs, 2, kv, d)
    return seq[:, :, 0], seq[:, :, 1]


def paged_prefill_reference(q, kv_pool, block_table, seg_ids, q_pos, kv_len,
                            *, host_pool=None, tier=None, tq=8,
                            softmax_scale=None):
    """Segmented GQA prefill attention straight over a paged KV pool
    (oracle for `paged_prefill.paged_prefill_pallas`).

    The token batch is a flat concatenation of per-request *segments*: a
    prefill chunk contributes its chunk tokens (a decode token is the
    degenerate one-token segment), each padded to a multiple of the query
    tile `tq` so a tile never straddles two segments — the same layout
    contract as the Pallas kernel. Every query attends causally against
    its segment's KV **in the pool** (the chunk's own KV must already be
    scattered in) — no dense prefix gather, no staging buffer. KV is
    gathered per query TILE (T/tq rows), not per token, so the oracle's
    memory traffic is O(T/tq * MAXB*BS), mirroring the kernel's per-tile
    block chase.

    q:           (T, H, D)   flat token batch, T % tq == 0 (padding rows
                 allowed; their outputs are garbage the caller discards)
    kv_pool:     (NB, 2, KV, BS, D) device pool; [:, 0/1] = K/V
    block_table: (S, MAXB) int32 physical block ids per segment
    seg_ids:     (T,) int32 segment of each token
    q_pos:       (T,) int32 absolute position of each token in its sequence
    kv_len:      (S,) int32 valid tokens per segment (prefix + chunk)
    host_pool/tier: when `tier` (S,) bool marks a segment's blocks as
                 host-resident, its KV is gathered from `host_pool` instead
                 (layer-wise offload mid-prefill). Both pools are gathered
                 and selected — fine for the oracle, 2x traffic.
    returns      (T, H, D)
    """
    T, H, D = q.shape
    S, MAXB = block_table.shape
    KV, BS = kv_pool.shape[2], kv_pool.shape[3]
    G = H // KV
    NT = T // tq
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    tile_seg = seg_ids.reshape(NT, tq)[:, 0]
    tab_t = block_table[tile_seg]            # (NT, MAXB)
    # a host-resident segment's ids index the HOST pool and vice versa —
    # clamp the not-applicable gather into range, `where` discards it
    g = kv_pool[jnp.minimum(tab_t, kv_pool.shape[0] - 1)]
    if tier is not None:                     # (NT, MAXB, 2, KV, BS, D)
        gh = host_pool[jnp.minimum(tab_t, host_pool.shape[0] - 1)]
        tt = tier[tile_seg]
        g = jnp.where(tt[:, None, None, None, None, None], gh, g)
    k, v = _pages_to_seq(g)                  # (NT, MAXB*BS, KV, D)
    qh = (q * scale).reshape(NT, tq, KV, G, D)
    logits = jnp.einsum("ntkgd,nskd->nkgts", qh, k).astype(jnp.float32)
    k_pos = jnp.arange(MAXB * BS)
    qp = q_pos.reshape(NT, tq)
    mask = (qp[:, :, None] >= k_pos[None, None]) \
        & (k_pos[None, None] < kv_len[tile_seg][:, None, None])
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)      # (NT, KV, G, tq, Skv)
    out = jnp.einsum("nkgts,nskd->ntkgd", p.astype(v.dtype), v)
    return out.reshape(T, H, D)


def paged_attention_reference(q, kv_pool, block_table, kv_len, *,
                              softmax_scale=None):
    """Decode GQA attention over a paged KV pool (oracle for the Pallas kernel).

    q:           (B, H, D)       one query token per sequence
    kv_pool:     (N_blocks, 2, KV, BS, D)  single pooled tensor (paper §4),
                 [:, 0] = K, [:, 1] = V
    block_table: (B, MAX_BLOCKS) int32 physical block ids (padding: any id —
                 masked out by kv_len)
    kv_len:      (B,) valid token count per sequence
    returns      (B, H, D)
    """
    # Gather per-sequence K/V: (B, MAX_BLOCKS, 2, KV, BS, D)
    k, v = _pages_to_seq(kv_pool[block_table])
    out = mha_reference(q[:, None], k, v, causal=False, kv_len=kv_len,
                        softmax_scale=softmax_scale)
    return out[:, 0]
