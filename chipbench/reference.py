"""Plain reference of the served models, for the `correct` check.

A straightforward float32 decoder, written from the configuration file
alone: RMSNorm, rotary embeddings on the first `partial_rotary_factor`
of each head (rotate-half pairing), grouped-query causal attention with
scale 1/sqrt(head_dim), gated SiLU MLP, and the LM head (tied to the
embedding where the configuration says so). It imports nothing of the
program. Its weights are the benchmark's own (`weights.py`), widened one
layer at a time inside a scan, so a model whose bf16 weights fill most of
the chip still fits beside them.

`teacher_forced_logits` runs the model once over a prompt and the tokens
served for it and returns the logits that predicted each served token.
`precision="fp8"` is the control: every matmul operand rounded through
float8 e4m3 with a per-tensor scale, the step below bf16 that a later
change could be tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 512       # query rows per attention block
SEQ_BUCKET = 2048  # sequence lengths pad to a multiple of this

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _fp8(x):
    """Round through float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, rot: int, theta: float):
    """x (S, N, hd) float32: rotate the first `rot` dims of each head as
    pairs (i, i + rot/2); pass the rest through."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv           # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layer(d: dict, precision: str, x, lp):
    """One decoder layer over the whole (padded) sequence x (S, dm)."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    S = x.shape[0]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    G = H // KV
    eps = d["norm_eps"]
    rot = int(hd * d["partial_rotary_factor"])
    pos = jnp.arange(S)
    a = lp["attn"]
    h = _rmsnorm(x, lp["attn_norm"]["w"], eps)
    k = _mm(h, a["wk"], precision)
    v = _mm(h, a["wv"], precision)
    if "bk" in a:
        k, v = k + a["bk"], v + a["bv"]
    k = _rope(k.reshape(S, KV, hd), pos, rot, d["rope_theta"])
    v = v.reshape(S, KV, hd)
    nb = S // QBLOCK

    def block(i):
        q0 = i * QBLOCK
        xb = jax.lax.dynamic_slice_in_dim(x, q0, QBLOCK)
        hb = jax.lax.dynamic_slice_in_dim(h, q0, QBLOCK)
        q = _mm(hb, a["wq"], precision)
        if "bq" in a:
            q = q + a["bq"]
        qpos = q0 + jnp.arange(QBLOCK)
        q = _rope(q.reshape(QBLOCK, H, hd), qpos, rot, d["rope_theta"])
        q = q.reshape(QBLOCK, KV, G, hd) * hd ** -0.5
        s = jnp.einsum("qkgd,tkd->kgqt", q, k,
                       precision=jax.lax.Precision.HIGHEST)
        mask = pos[None, :] <= qpos[:, None]                 # (q, t)
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        xb = xb + _mm(o.reshape(QBLOCK, H * hd), a["wo"], precision)
        m = lp["mlp"]
        hb = _rmsnorm(xb, lp["mlp_norm"]["w"], eps)
        f = jax.nn.silu(_mm(hb, m["wg"], precision)) \
            * _mm(hb, m["wu"], precision)
        return xb + _mm(f, m["wd"], precision)

    return jax.lax.map(block, jnp.arange(nb)).reshape(S, -1)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _forward(dkey, precision, weights, tokens, n_out, first):
    d = dict(dkey)
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(
        lambda c, lp: (_layer(d, precision, c, lp), None), x,
        weights["layers"])
    xs = jax.lax.dynamic_slice_in_dim(x, first, n_out)
    xs = _rmsnorm(xs, weights["final_norm"]["w"].astype(jnp.float32),
                  d["norm_eps"])
    w = weights["embed"].T if d["tie_embeddings"] else weights["lm_head"]
    logits = _mm(xs, w.astype(jnp.float32), precision)
    return logits[:, :d["vocab_size"]]


def teacher_forced_logits(d: dict, weights, prompt, served,
                          precision: str = "f32") -> np.ndarray:
    """(len(served), vocab) float32 logits: row i is what the model
    predicts after `prompt + served[:i]`."""
    seq = list(prompt) + list(served[:-1])
    S = len(seq)
    Sp = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    toks = np.zeros(Sp, np.int32)
    toks[:S] = seq
    n = len(served)
    # the output slice has a fixed length per padded size so each bucket
    # compiles once; rows past n are dropped here
    n_pad = min(Sp, -(-n // SEQ_BUCKET) * SEQ_BUCKET)
    first = min(len(prompt) - 1, Sp - n_pad)
    dkey = tuple(sorted(d.items()))
    out = _forward(dkey, precision, weights, jnp.asarray(toks), n_pad,
                   jnp.int32(first))
    off = len(prompt) - 1 - first
    return np.asarray(out[off:off + n])


def widest_gap(logits: np.ndarray, chosen) -> float:
    """Largest amount by which a chosen token's logit lies below the best
    logit of its row; a token outside the vocabulary reads infinite."""
    chosen = np.asarray(chosen)
    V = logits.shape[1]
    if chosen.size == 0:
        return 0.0
    if (chosen < 0).any() or (chosen >= V).any():
        return float("inf")
    rows = np.arange(len(chosen))
    return float(np.max(logits.max(axis=1) - logits[rows, chosen]))
