"""Pallas TPU kernel writing one token's KV per row into the paged pool.

The decode step appends each row's new K/V to its current page. As an
XLA scatter that write wants the pool in a layout of its own, unlike
the paged attention kernel's, so the compiler relayouts (copies) the
whole pool around every layer's scatter. This kernel writes in place
instead: the pool is aliased from input to output, and each grid step
reads, blends and writes back one small window of one page.

Pool layout is head-major, `(NB, 2, KV, BS, D)` (`paged_attention.py`).
Grid = (R,), one row per step; `blk` and `off` are scalar-prefetched so
the BlockSpec index map picks the row's window `(1, 2, KV, W, D)` at
`(blk[r], 0, 0, off[r] // W, 0)`. The body selects the new token at
`off % W` with an iota mask and keeps every other element as it was.

Window `W`: the smallest slice of the page's token axis Mosaic tiles
for the dtype, 16 rows for 16-bit pools and 8 for 32-bit ones, or the
whole page where it is shorter. It follows from the pool's shape and
dtype alone, so every block size and head dim takes this one kernel.

Contract: rows write distinct `(block, window)` pairs, except padded
rows, which all write the trash block. The window pipeline neither
re-fetches nor writes back a window that consecutive grid steps share,
and may fetch a later step's window before an earlier step's write has
landed, so two real rows in one window would lose a write. The decode
step meets it: each request writes its own current page. Chunked and
fused prefill write consecutive tokens of one page and keep the XLA
scatter (`ref.paged_kv_write_reference`).

Manual single-row DMAs do not compile: Mosaic rejects a one-row bf16
slice (not aligned to its tiling) and a 64-lane slice of an HBM ref,
which it sees padded to 128 lanes; BlockSpec windows avoid both.

Compiled by Mosaic on a TPU; elsewhere callers must pass
`interpret=True`. Validated bit for bit against the scatter oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def window_rows(pool) -> int:
    """Token rows of the page each grid step reads and writes back."""
    BS = pool.shape[3]
    return min(BS, 8 if jnp.dtype(pool.dtype).itemsize >= 4 else 16)


def _kv_write_kernel(blk_ref, off_ref, k_ref, v_ref, pool_ref, o_ref, *, w):
    del blk_ref
    slot = off_ref[pl.program_id(0)] % w
    for i, new_ref in ((0, k_ref), (1, v_ref)):
        cur = pool_ref[0, i].astype(jnp.float32)              # (KV, W, D)
        new = jnp.broadcast_to(new_ref[0].astype(jnp.float32), cur.shape)
        hit = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1) == slot
        o_ref[0, i] = jnp.where(hit, new, cur).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write_pallas(pool, blk, off, k, v, *, interpret=False):
    """pool: (NB, 2, KV, BS, D); blk, off: (R,) int32; k, v: (R, KV, D).
    Returns the pool with row r's K/V at `pool[blk[r], 0/1, :, off[r]]`
    and every other element unchanged. Rows must hit distinct
    `(block, window)` pairs but for padded rows on the trash block (see
    the module docstring)."""
    _, _, KV, _, D = pool.shape
    R = blk.shape[0]
    W = window_rows(pool)
    # (R, KV, 1, D): a row's token is a one-row (1, D) tile per head
    k4 = k.astype(pool.dtype)[:, :, None]
    v4 = v.astype(pool.dtype)[:, :, None]
    row = pl.BlockSpec((1, KV, 1, D), lambda r, blk, off: (r, 0, 0, 0))
    window = pl.BlockSpec((1, 2, KV, W, D),
                          lambda r, blk, off: (blk[r], 0, 0, off[r] // W, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[row, row, window],
        out_specs=window,
    )
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, w=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands count the two scalar-prefetch arrays: the pool is 4
        input_output_aliases={4: 0},
        interpret=interpret,
    )(blk.astype(jnp.int32), off.astype(jnp.int32), k4, v4, pool)
