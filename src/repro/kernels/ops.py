"""Attention entry points, with the implementation picked by platform.

On a TPU the paged ops (`paged_attention`, `paged_prefill`) run the
compiled Pallas kernels; on any other platform they run the pure-jnp
oracles in `ref.py`, which stay the test oracle. Dense attention
(`flash_attention`, `decode_attention`) is always the jnp implementation,
XLA-compiled on every platform. There is no interpreter fallback: the
Pallas kernels interpret only when a caller passes `interpret=True`.

`dispatched` counts, at trace time, which implementation each op lowered
to, keyed by (op, impl) — a compiled step's count shows what it runs.
"""
from __future__ import annotations

import collections

import jax

from repro.kernels import ref as _ref

dispatched: collections.Counter = collections.Counter()


def paged_impl() -> str:
    """'pallas' on a TPU, else 'ref'."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def flash_attention(q, k, v, *, causal=True, window=0, kv_len=None, q_offset=0,
                    q_chunk=512, kv_chunk=512, softmax_scale=None):
    dispatched["flash_attention", "ref"] += 1
    return _ref.flash_attention_reference(
        q, k, v, causal=causal, window=window, kv_len=kv_len,
        q_offset=q_offset, q_chunk=q_chunk, kv_chunk=kv_chunk,
        softmax_scale=softmax_scale)


def decode_attention(q, k_cache, v_cache, kv_len, *, window=0,
                     softmax_scale=None):
    dispatched["decode_attention", "ref"] += 1
    return _ref.decode_attention_reference(
        q, k_cache, v_cache, kv_len, window=window,
        softmax_scale=softmax_scale)


def paged_attention(q, kv_pool, block_table, kv_len, *, softmax_scale=None):
    impl = paged_impl()
    dispatched["paged_attention", impl] += 1
    if impl == "pallas":
        from repro.kernels import paged_attention as _pa
        return _pa.paged_attention_pallas(
            q, kv_pool, block_table, kv_len, softmax_scale=softmax_scale)
    return _ref.paged_attention_reference(
        q, kv_pool, block_table, kv_len, softmax_scale=softmax_scale)


def paged_prefill(q, kv_pool, block_table, seg_ids, q_pos, kv_len, *,
                  host_pool=None, tier=None, tq=8, softmax_scale=None):
    """Segmented prefill/decode attention straight over the paged pool(s).

    q: (T, H, D) flat token batch — per-request segments each padded to a
    multiple of `tq` (so a query tile never straddles segments); the
    chunk's own KV must already be scattered into the pool. block_table:
    (S, MAXB); seg_ids/q_pos: (T,); kv_len: (S,). With `tier` (S,) bool,
    a True segment's blocks are read from `host_pool`. Returns (T, H, D).
    """
    impl = paged_impl()
    op = "paged_prefill" if tier is None else "paged_prefill_tiered"
    dispatched[op, impl] += 1
    if impl == "pallas":
        from repro.kernels import paged_prefill as _pp
        return _pp.paged_prefill_pallas(
            q, kv_pool, block_table, seg_ids, q_pos, kv_len,
            host_pool=host_pool, tier=tier, tq=tq,
            softmax_scale=softmax_scale)
    return _ref.paged_prefill_reference(
        q, kv_pool, block_table, seg_ids, q_pos, kv_len,
        host_pool=host_pool, tier=tier, tq=tq, softmax_scale=softmax_scale)
