"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in Pallas interpret mode on CPU (asked for explicitly: a
compiled Pallas call off-TPU raises)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_attention_pallas
from repro.kernels.kv_write import paged_kv_write_pallas, window_rows
from repro.kernels.paged_attention import paged_attention_pallas

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, Skv, H, KV, D, dtype):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (B, Sq, H, D)).astype(dtype)
    k = jax.random.normal(kk, (B, Skv, KV, D)).astype(dtype)
    v = jax.random.normal(kv, (B, Skv, KV, D)).astype(dtype)
    return q, k, v


# ---------------------------------------------------------------- flash ----

@pytest.mark.parametrize("H,KV", [(8, 8), (8, 2), (4, 1), (28, 4)])
@pytest.mark.parametrize("S", [128, 384])
def test_flash_gqa_shapes(H, KV, S):
    q, k, v = _qkv(2, S, S, H, KV, 64, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_dtypes(dtype, tol):
    q, k, v = _qkv(1, 256, 256, 4, 2, 128, dtype)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [1, 17, 64, 1000])
def test_flash_sliding_window(window):
    q, k, v = _qkv(2, 256, 256, 4, 4, 64, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=64, block_k=64, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bk", [(32, 128), (128, 32), (256, 256)])
def test_flash_block_shapes(bq, bk):
    q, k, v = _qkv(1, 256, 256, 4, 2, 64, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                 block_k=bk, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_flash_ref_chunked_matches_unchunked():
    q, k, v = _qkv(2, 512, 512, 8, 2, 64, jnp.float32)
    a = ref.flash_attention_reference(q, k, v, causal=True, q_chunk=128,
                                      kv_chunk=64)
    b = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_ref_kv_len_mask():
    q, k, v = _qkv(3, 64, 64, 4, 4, 32, jnp.float32)
    kv_len = jnp.array([3, 33, 64])
    a = ref.flash_attention_reference(q, k, v, causal=True, kv_len=kv_len,
                                      q_chunk=32, kv_chunk=32)
    b = ref.mha_reference(q, k, v, causal=True, kv_len=kv_len)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- paged ----

@pytest.mark.parametrize("H,KV", [(8, 8), (8, 2), (16, 1), (12, 4)])
@pytest.mark.parametrize("BS", [8, 16])
def test_paged_attention_shapes(H, KV, BS):
    B, D, NB, MAXB = 3, 64, 64, 6
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    pool = jax.random.normal(kp, (NB, 2, KV, BS, D), jnp.float32)
    tab = jax.random.permutation(KEY, NB)[:B * MAXB].reshape(B, MAXB)
    tab = tab.astype(jnp.int32)
    kv_len = jnp.array([1, BS * 2 + 3, BS * MAXB], jnp.int32)
    out = paged_attention_pallas(q, pool, tab, kv_len, interpret=True)
    expect = ref.paged_attention_reference(q, pool, tab, kv_len)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_paged_attention_bf16():
    B, H, KV, D, NB, BS, MAXB = 2, 8, 2, 128, 32, 16, 4
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (B, H, D)).astype(jnp.bfloat16)
    pool = jax.random.normal(kp, (NB, 2, KV, BS, D)).astype(jnp.bfloat16)
    tab = jnp.arange(B * MAXB, dtype=jnp.int32).reshape(B, MAXB)
    kv_len = jnp.array([17, 64], jnp.int32)
    out = paged_attention_pallas(q, pool, tab, kv_len, interpret=True)
    expect = ref.paged_attention_reference(q, pool, tab, kv_len)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32),
                               atol=3e-2, rtol=3e-2)


def test_paged_matches_dense_decode():
    """Paged attention over scattered blocks == dense-cache decode."""
    B, H, KV, D, BS = 2, 8, 4, 32, 8
    S = 40
    MAXB = S // BS
    NB = B * MAXB + 7
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)
    # scatter into a shuffled pool
    perm = np.random.RandomState(0).permutation(NB)[:B * MAXB]
    pool = np.zeros((NB, 2, KV, BS, D), np.float32)
    tab = perm.reshape(B, MAXB)
    for b in range(B):
        for i in range(MAXB):
            # head-major page: (KV, BS, D) per K/V
            pool[tab[b, i], 0] = np.asarray(
                k[b, i * BS:(i + 1) * BS]).transpose(1, 0, 2)
            pool[tab[b, i], 1] = np.asarray(
                v[b, i * BS:(i + 1) * BS]).transpose(1, 0, 2)
    kv_len = jnp.array([S - 5, S], jnp.int32)
    out = paged_attention_pallas(q, jnp.asarray(pool),
                                 jnp.asarray(tab, jnp.int32), kv_len,
                                 interpret=True)
    expect = ref.decode_attention_reference(q[:, None], k, v, kv_len)[:, 0]
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- kv write ---

def _kv_rows(pool, n_pad):
    """Rows at offsets 0, W-1, W and BS-1 of distinct blocks, then
    `n_pad` padded rows on the trash block (the pool's last)."""
    NB, _, KV, BS, D = pool.shape
    W = window_rows(pool)
    offs = [0, W - 1, W % BS, BS - 1]
    blk = [1, 3, 4, NB - 2] + [NB - 1] * n_pad
    kk, kv = jax.random.split(jax.random.PRNGKey(7))
    k = jax.random.normal(kk, (len(offs), KV, D), jnp.float32)
    v = jax.random.normal(kv, (len(offs), KV, D), jnp.float32)
    # padded rows carry one garbage token, as the decode step's do
    pad = lambda a: jnp.concatenate([a, jnp.repeat(a[:1], n_pad, 0)])
    return (jnp.array(blk, jnp.int32), jnp.array(offs + [0] * n_pad,
                                                 jnp.int32), pad(k), pad(v))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("BS", [8, 16, 128])
@pytest.mark.parametrize("KV,D", [(8, 64), (32, 128)])
def test_paged_kv_write_matches_scatter(KV, D, BS, dtype):
    """The in-place window write leaves the whole pool bit-identical to
    the XLA scatter, trash block included."""
    pool = jax.random.normal(KEY, (8, 2, KV, BS, D)).astype(dtype)
    blk, off, k, v = _kv_rows(pool, n_pad=3)
    out = paged_kv_write_pallas(pool, blk, off, k, v, interpret=True)
    expect = ref.paged_kv_write_reference(pool, blk, off, k, v)
    assert out.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(expect, np.float32))
    # the rows landed: nothing was written twice or dropped
    got = np.asarray(out[blk[:4], 0, :, off[:4]], np.float32)
    np.testing.assert_array_equal(
        got, np.asarray(k[:4].astype(dtype), np.float32))


def test_kv_write_window_rule():
    """16 token rows per window for 16-bit pools, 8 for 32-bit, at most
    the page."""
    shape = lambda bs: (2, 2, 8, bs, 64)
    assert window_rows(jnp.zeros(shape(128), jnp.bfloat16)) == 16
    assert window_rows(jnp.zeros(shape(128), jnp.float32)) == 8
    assert window_rows(jnp.zeros(shape(8), jnp.bfloat16)) == 8
    assert window_rows(jnp.zeros(shape(16), jnp.float32)) == 8


# --------------------------------------------------------------- rmsnorm ---

@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 256), (3, 33, 512)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_rmsnorm_kernel(shape, dtype, tol):
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.models.layers import rmsnorm
    x = jax.random.normal(KEY, shape).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(9), shape[-1:]) * 0.1
         + 1.0).astype(dtype)
    out = rmsnorm_pallas(x, w, block_rows=8, interpret=True)
    expect = rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


# ------------------------------------------------------ platform dispatch --

def test_ops_picks_ref_off_tpu():
    """Off-TPU the paged ops run the jnp oracle, bit for bit, and the
    trace-time record says so."""
    from repro.kernels import ops
    B, H, KV, D, NB, BS, MAXB = 2, 8, 2, 32, 16, 8, 3
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    pool = jax.random.normal(kp, (NB, 2, KV, BS, D), jnp.float32)
    tab = jnp.arange(B * MAXB, dtype=jnp.int32).reshape(B, MAXB)
    kv_len = jnp.array([5, 20], jnp.int32)
    assert ops.paged_impl() == "ref"
    before = ops.dispatched["paged_attention", "ref"]
    out = ops.paged_attention(q, pool, tab, kv_len)
    assert ops.dispatched["paged_attention", "ref"] == before + 1
    assert ops.dispatched["paged_attention", "pallas"] == 0
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref.paged_attention_reference(q, pool, tab, kv_len)))


def test_ops_paged_kv_write_picks_ref_off_tpu():
    """Off-TPU the decode step's KV write is the XLA scatter, and the
    trace-time record says so."""
    from repro.kernels import ops
    pool = jax.random.normal(KEY, (8, 2, 2, 16, 32), jnp.float32)
    blk, off, k, v = _kv_rows(pool, n_pad=0)
    before = ops.dispatched["paged_kv_write", "ref"]
    out = ops.paged_kv_write(pool, blk, off, k, v)
    assert ops.dispatched["paged_kv_write", "ref"] == before + 1
    assert ops.dispatched["paged_kv_write", "pallas"] == 0
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref.paged_kv_write_reference(pool, blk, off, k, v)))


def _uninterpreted_calls():
    """One compiled-mode call per Pallas kernel, at tiny shapes."""
    from repro.kernels.paged_prefill import paged_prefill_pallas
    from repro.kernels.rmsnorm import rmsnorm_pallas
    q = jnp.zeros((2, 8, 32))
    pool = jnp.zeros((8, 2, 2, 8, 32))
    tab = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.array([3, 5], jnp.int32)
    qp = jnp.zeros((8, 8, 32))
    seg = jnp.zeros((8,), jnp.int32)
    pos = jnp.arange(8, dtype=jnp.int32)
    qkv = _qkv(1, 64, 64, 4, 2, 32, jnp.float32)
    return {
        "paged_attention": lambda: paged_attention_pallas(q, pool, tab, lens),
        "paged_prefill": lambda: paged_prefill_pallas(
            qp, pool, tab[:1], seg, pos, lens[:1]),
        "paged_prefill_tiered": lambda: paged_prefill_pallas(
            qp, pool, tab[:1], seg, pos, lens[:1], host_pool=pool,
            tier=jnp.array([True])),
        "paged_kv_write": lambda: paged_kv_write_pallas(
            pool, tab[0], lens, q[:, :2], q[:, :2]),
        "flash_attention": lambda: flash_attention_pallas(*qkv),
        "rmsnorm": lambda: rmsnorm_pallas(jnp.ones((8, 128)),
                                          jnp.ones((128,))),
    }


@pytest.mark.parametrize("kernel", ["paged_attention", "paged_prefill",
                                    "paged_prefill_tiered", "paged_kv_write",
                                    "flash_attention", "rmsnorm"])
def test_pallas_off_tpu_without_interpret_raises(kernel):
    """No kernel picks interpret mode for itself: a compiled call on a
    host without a TPU is an error, never a silent interpreter run."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="interpret"):
        _uninterpreted_calls()[kernel]()
