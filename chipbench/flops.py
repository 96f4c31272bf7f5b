"""Operation and byte counts of the served model, from its sizes alone.

The benchmark's own arithmetic (no import of the program): what one
prefill or one decode step must compute for its REAL tokens and
contexts, with the program's padding left out. `dims` is the dict that
`common.model_dims` builds from a configuration file.
"""
from __future__ import annotations

from typing import Iterable


def matmul_params(d: dict) -> int:
    """Weights each token multiplies once in the layer stack: Q, K, V, O
    projections and the gated MLP (3 matrices). The LM head is counted by
    `head_flops`; embedding lookups and norms are not matmuls."""
    dm, hd = d["d_model"], d["head_dim"]
    attn = dm * d["n_heads"] * hd + 2 * dm * d["n_kv_heads"] * hd \
        + d["n_heads"] * hd * dm
    mlp = 3 * dm * d["d_ff"]
    return d["n_layers"] * (attn + mlp)


def head_flops(d: dict) -> int:
    return 2 * d["d_model"] * d["vocab_size"]


def attn_flops(d: dict, q_tokens: int, ctx_sum: int) -> int:
    """QK^T and PV over `ctx_sum` (query, key) pairs in every layer."""
    return 4 * d["n_layers"] * d["n_heads"] * d["head_dim"] * ctx_sum \
        if q_tokens else 0


def prefill_flops(d: dict, prompt_len: int) -> int:
    """One exclusive prefill: every prompt token through every layer,
    causal attention (token i attends to i + 1 keys), LM head on the last
    token only."""
    P = prompt_len
    pairs = P * (P + 1) // 2
    return 2 * matmul_params(d) * P + attn_flops(d, P, pairs) \
        + head_flops(d)


def decode_flops(d: dict, ctxs: Iterable[int]) -> int:
    """One decode step: each request's new token through every layer and
    the LM head, attending to its `ctx` cached tokens plus itself."""
    ctxs = list(ctxs)
    R = len(ctxs)
    return R * (2 * matmul_params(d) + head_flops(d)) \
        + attn_flops(d, R, sum(c + 1 for c in ctxs))


def paged_attention_cost(d: dict, ctxs: Iterable[int],
                         kv_bytes: int = 2) -> tuple:
    """(flops, bytes) the decode attention kernel needs in one step over
    all layers: read each request's K and V for its `ctx + 1` live
    tokens, read its queries and write its outputs."""
    L, H, KV, hd = d["n_layers"], d["n_heads"], d["n_kv_heads"], \
        d["head_dim"]
    ctxs = list(ctxs)
    live = sum(c + 1 for c in ctxs)
    flops = 4 * L * H * hd * live
    nbytes = L * (2 * KV * hd * kv_bytes * live
                  + 2 * len(ctxs) * H * hd * kv_bytes)
    return flops, nbytes
