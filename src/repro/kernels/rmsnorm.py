"""Pallas TPU fused RMSNorm kernel.

Norms run twice per layer per token in decode — at batch 128 that is
~10k launches/s of a bandwidth-bound op, worth fusing into one
VMEM-resident pass (read x once, write once; the f32 accumulation for the
mean-square lives in registers).

Grid: one program per row-tile; d_model rides whole in the lane dim
(128-aligned for every assigned arch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)          # (rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) \
        * w_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x, w, *, eps=1e-6, block_rows=128, interpret=False):
    """x: (..., d); w: (d,). Returns rmsnorm(x) * w in x.dtype."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, [(0, pad), (0, 0)])
    n = x2.shape[0] // br
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, w)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
