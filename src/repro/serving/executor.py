"""Real JAX executor for the serving engine: paged KV pools + jitted steps.

Physical layout follows the paper's §4: ONE pooled tensor per memory tier
(device / host), shared by all layers — head-major `(num_blocks, 2, KV,
block_size, hd)`, so a Pallas kernel fetches one head of one page as a
whole `(block_size, hd)` tile — and any physical block can hold any
(request, layer) slice; logical placement lives in the block manager.
Each pool carries ONE extra physical block (`trash_block`) that the block
manager never hands out: padded batch rows scatter their garbage KV
there, which is what lets every jitted entry point run on shape-bucketed
(power-of-two padded) batches.

Bucketed-shape contract: `prefill` pads the prompt buffer, `decode` the
batch width R, and `mixed_step` the chunk rows Tc / chunk segments Sc /
decode width Rb / output rows Sb — all to power-of-two buckets — while
block-table widths round to 8-block granularity; steady-state serving
triggers zero retraces. Every novel jit signature is counted in
`jit_retraces` and logged.

Decoder-only families (dense / moe) — the families the paper evaluates.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.ref import paged_kv_write_reference as _scatter_tokens
from repro.models import build_model, layers
from repro.models.model import _mask_pad_logits
from repro.obs.registry import MetricsRegistry
from repro.obs.spans import span

log = logging.getLogger(__name__)

# query-tile granularity of the fused mixed step: every chunk segment's
# tokens are padded to a multiple of TQ so a query tile never straddles
# two segments. 32 covers the default chunk budget in ONE tile — the ref
# backend gathers a segment's KV once per tile, and the Pallas kernel
# amortizes its block chase over the whole tile — at the cost of up to
# TQ-1 padded rows of extra (cheap) weight-stream compute per chunk
MIXED_TQ = 32


def _round_up(n, m):
    return -(-n // m) * m


def _program(name: str):
    """Give a served jitted program the stable name `name`, whatever
    the method is called: the device trace's "XLA Modules" line shows
    its device time as `jit_<name>`, and the benchmark reads it there."""
    def named(fn):
        fn.__name__ = name
        return fn
    return named


def _bucket(n: int, lo: int = 1) -> int:
    """Smallest power-of-two >= n (and >= lo) — the jit shape bucket."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class MixedChunk:
    """One prefill chunk riding the fused mixed step."""
    tokens: List[int]        # chunk token ids
    offset: int              # absolute position of tokens[0] (= prefill_done)
    tables: List[List[int]]  # per-layer LIVE block ids — only the
    #                          ceil((offset + len(tokens)) / BS) blocks that
    #                          hold valid KV, never the full allocation
    tiers: List[bool]        # per-layer: True = blocks live in the HOST pool


@dataclasses.dataclass
class MixedDecode:
    """One decode token riding the fused mixed step."""
    token: int               # last generated token (the step's input)
    ctx: int                 # tokens already cached; KV grows to ctx + 1
    tables: List[List[int]]  # per-layer DEVICE block ids


class PagedExecutor:
    """Owns the physical KV pools (device + host buffers, paged in
    `block_size`-token blocks) and runs model forwards against them:
    batched prefill, paged decode, chunked prefill, and the fused
    `mixed_step`. Pure mechanism — which blocks a request may touch is
    decided upstream by `SchedulerCore`/`LayerwiseBlockManager`."""

    def __init__(self, cfg: ModelConfig, params, num_device_blocks: int,
                 num_host_blocks: int, block_size: int, rng=None,
                 device=None):
        """`device` pins params, both pools and therefore every jitted
        step (they follow their committed inputs) to one device; None
        leaves them on the default device."""
        assert cfg.family in ("dense", "moe"), cfg.family
        self.cfg = cfg
        self.model = build_model(cfg)
        if params is None:
            with jax.default_device(device):
                params = self.model.init(
                    rng if rng is not None else jax.random.PRNGKey(0))
        self.params = params if device is None \
            else jax.device_put(params, device)
        hd = cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        self.block_size = block_size
        self.num_device_blocks = num_device_blocks
        self.num_host_blocks = num_host_blocks
        # +1: the trash block (id == num_*_blocks) absorbing padded rows'
        # scatter writes; the block manager never allocates it and no
        # block table with kv_len > 0 ever reads it
        self.device_pool = jnp.zeros(
            (num_device_blocks + 1, 2, cfg.n_kv_heads, block_size, hd), dt,
            device=device)
        self.host_pool = jnp.zeros(
            (num_host_blocks + 1, 2, cfg.n_kv_heads, block_size, hd), dt,
            device=device)
        # bytes of one block of either pool (the pools share a layout)
        self.block_nbytes = self.device_pool.nbytes \
            // self.device_pool.shape[0]
        self._decode_fn = jax.jit(self._paged_decode,
                                  donate_argnames=("dpool",))
        self._prefill_fn = jax.jit(self._prefill)
        # retrace accounting: every novel (entry point, shape bucket)
        # signature is one XLA compile mid-serving — the bucketing above
        # exists to keep these counters flat in steady state. Counts
        # live in the obs registry; the owning engine swaps in the
        # core's registry so one snapshot() carries both.
        self.registry = MetricsRegistry()
        self._jit_sigs: set = set()

    @property
    def jit_retraces(self) -> collections.Counter:
        """Retrace counts per entry point (registry-backed Counter —
        the historical attribute shape)."""
        return self.registry.counter_view("jit_retraces", "fn")

    def _note_trace(self, fn: str, sig: tuple) -> None:
        if (fn, sig) not in self._jit_sigs:
            self._jit_sigs.add((fn, sig))
            self.registry.inc("jit_retraces", fn=fn)
            log.info("jit retrace #%d for %s%s",
                     int(self.registry.get("jit_retraces", fn=fn)),
                     fn, sig)

    # -------------------------------------------------------------- prefill
    @_program("serve_prefill")
    def _prefill(self, params, batch, cache):
        return self.model.prefill(params, batch, cache, dropless=True)

    def prefill(self, prompt: List[int], pad_to: int):
        """Run one request's prefill (B=1). `pad_to` is bucketed to the
        next power of two so novel prompt lengths reuse a compiled shape.
        Returns (next_token, k_layers, v_layers) with shapes
        (L, S_bucket, KV, hd); only the first len(prompt) positions are
        valid (callers slice what they need)."""
        S = len(prompt)
        pad_to = _bucket(pad_to, 16)
        with span("exec.prefill.launch", tokens=S, tokens_padded=pad_to):
            self._note_trace("prefill", (pad_to,))
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :S] = prompt
            batch = {"tokens": jnp.asarray(toks),
                     "prompt_len": jnp.asarray([S], jnp.int32)}
            cache = self.model.init_cache(1, pad_to, self.cfg.dtype)
            logits, cache = self._prefill_fn(self.params, batch, cache)
            k = cache["k"][:, 0]  # (L, S_bucket, KV, hd)
            v = cache["v"][:, 0]
        with span("exec.prefill.wait"):
            next_tok = int(jnp.argmax(logits[0]))
        return next_tok, k, v

    # ---------------------------------------------------------- pool writes
    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    @_program("kv_write")
    def _scatter_layer(self, pool, block_ids, k, v):
        """Write one layer's KV (S_pad, KV, hd) into `pool` blocks.
        block_ids: (nb,) int32; S_pad == nb * block_size."""
        nb = block_ids.shape[0]
        BS = pool.shape[3]
        kv = jnp.stack([k, v], axis=1).astype(pool.dtype)  # (S_pad,2,KV,hd)
        kv = kv.reshape(nb, BS, *kv.shape[1:]).transpose(0, 2, 3, 1, 4)
        return pool.at[block_ids].set(kv)  # (nb, 2, KV, BS, hd) pages

    def write_layer(self, tier: str, block_ids: List[int], k, v):
        nb = len(block_ids)
        with span("exec.kv.write", tier=tier, blocks=nb,
                  bytes=nb * self.block_nbytes):
            ids = jnp.asarray(block_ids, jnp.int32)
            S_pad = nb * self.block_size
            k = k[:S_pad]
            v = v[:S_pad]
            if tier == "device":
                self.device_pool = self._scatter_layer(
                    self.device_pool, ids, k, v)
            else:
                self.host_pool = self._scatter_layer(
                    self.host_pool, ids, k, v)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    @_program("kv_write")
    def _scatter_slice(self, pool, blk_ids, offs, k, v):
        """Write C tokens of one layer's KV into per-token (block, offset)
        slots — the partial-block append used by chunked prefill."""
        return _scatter_tokens(pool, blk_ids, offs, k, v)

    def write_layer_slice(self, tier: str, block_ids: List[int],
                          token_offset: int, k, v):
        """Append one layer's chunk KV (C, KV, hd) into `block_ids` starting
        at absolute token `token_offset` (need not be block-aligned). The
        span's `blocks` are the blocks the slice touches, its `bytes`
        the token rows written."""
        C = k.shape[0]
        BS = self.block_size
        with span("exec.kv.write", tier=tier,
                  blocks=(token_offset + C - 1) // BS - token_offset // BS
                  + 1, bytes=C * self.block_nbytes // BS):
            pos = np.arange(token_offset, token_offset + C)
            blk = jnp.asarray(np.asarray(block_ids, np.int32)[pos // BS])
            off = jnp.asarray(pos % BS, jnp.int32)
            if tier == "device":
                self.device_pool = self._scatter_slice(
                    self.device_pool, blk, off, k, v)
            else:
                self.host_pool = self._scatter_slice(
                    self.host_pool, blk, off, k, v)

    def gather_layer(self, tier: str, block_ids: List[int], kv_valid=None):
        """Dense (nb*BS, KV, hd) K and V views of one layer's block list —
        the contiguous prefix buffer legacy (two-call) chunked prefill and
        prefix-cache COW reads attend against. With `kv_valid` set, only
        the ceil(kv_valid / BS) blocks holding live tokens are physically
        gathered; the remaining rows come back zero (callers mask them via
        kv_len anyway), turning an O(allocated) copy into O(valid)."""
        pool = self.device_pool if tier == "device" else self.host_pool
        nb = len(block_ids)
        live = nb if kv_valid is None else min(
            _round_up(kv_valid, self.block_size) // self.block_size, nb)
        with span("exec.kv.gather", tier=tier, blocks=live,
                  bytes=live * self.block_nbytes):
            pages = pool[jnp.asarray(block_ids[:live], jnp.int32)]
            _, _, KV, BS, hd = pool.shape
            seq = pages.transpose(0, 3, 1, 2, 4).reshape(live * BS, 2, KV,
                                                         hd)
            k, v = seq[:, 0], seq[:, 1]
            if live < nb:
                pad = [(0, (nb - live) * self.block_size), (0, 0), (0, 0)]
                k = jnp.pad(k, pad)
                v = jnp.pad(v, pad)
            return k, v

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=2)
    @_program("kv_copy")
    def _copy_blocks(self, src, dst, src_ids, dst_ids):
        return dst.at[dst_ids].set(src[src_ids])

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    @_program("kv_copy")
    def _copy_blocks_within(self, pool, src_ids, dst_ids):
        """Same-pool copy (prefix-cache COW): a separate jit so the pool
        can still be donated — passing one buffer as both src and dst of
        `_copy_blocks` would alias a donated input."""
        return pool.at[dst_ids].set(pool[src_ids])

    def copy_blocks(self, src_tier: str, dst_tier: str, src_ids, dst_ids):
        """Physical block copy between (or within) tiers: d2h/h2d
        transfers and d2d copy-on-write duplication."""
        nb = len(src_ids)
        with span("exec.kv.copy", src=src_tier, dst=dst_tier, blocks=nb,
                  bytes=nb * self.block_nbytes):
            si = jnp.asarray(src_ids, jnp.int32)
            di = jnp.asarray(dst_ids, jnp.int32)
            if src_tier == dst_tier:
                if src_tier == "device":
                    self.device_pool = self._copy_blocks_within(
                        self.device_pool, si, di)
                else:
                    self.host_pool = self._copy_blocks_within(
                        self.host_pool, si, di)
                return
            src = self.device_pool if src_tier == "device" \
                else self.host_pool
            if dst_tier == "device":
                self.device_pool = self._copy_blocks(
                    src, self.device_pool, si, di)
            else:
                self.host_pool = self._copy_blocks(
                    src, self.host_pool, si, di)

    # ------------------------------------------------------- chunked prefill
    @functools.partial(jax.jit, static_argnums=0)
    @_program("serve_chunk")
    def _chunk_forward(self, params, tokens, kbuf, vbuf, offset, kv_valid):
        """One prefill chunk at absolute token `offset` — the LEGACY
        (two-call) chunk path. tokens: (C,) int32; kbuf/vbuf: (L, S_buf,
        KV, hd) dense prefix buffers gathered from the pools (rows >=
        offset ignored). Causal masking runs against the cached prefix via
        q_offset; kv_valid = offset + C masks the tail. Returns
        (last-position logits, k_chunk, v_chunk) with chunk KV shaped
        (L, C, KV, hd) for the caller to append into the pools. The fused
        path (`mixed_step`) replaces this with attention straight over the
        pools."""
        cfg = self.cfg
        C = tokens.shape[0]
        x = params["embed"][tokens][None]               # (1, C, d)
        positions = offset + jnp.arange(C)[None]        # (1, C)
        if cfg.pos_emb == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, 1, C))
        ks_out, vs_out = [], []
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = layers.apply_norm(cfg, lp["attn_norm"], x)
            q, k, v = layers.qkv_proj(cfg, lp["attn"], h)
            q = layers.apply_rope(cfg, q, positions)
            k = layers.apply_rope(cfg, k, positions)
            kb = jax.lax.dynamic_update_slice(
                kbuf[l], k[0].astype(kbuf.dtype), (offset, 0, 0))
            vb = jax.lax.dynamic_update_slice(
                vbuf[l], v[0].astype(vbuf.dtype), (offset, 0, 0))
            o = ops.flash_attention(q, kb[None], vb[None], causal=True,
                                    kv_len=kv_valid.reshape(1),
                                    q_offset=offset)
            x = x + layers.attn_out(cfg, lp["attn"], o)
            h = layers.apply_norm(cfg, lp["mlp_norm"], x)
            if cfg.family == "moe":
                from repro.models import moe as moe_mod
                f, _ = moe_mod.moe_ffn(cfg, lp["moe"], h, dropless=True)
            else:
                f = layers.mlp(cfg, lp["mlp"], h)
            x = x + f
            ks_out.append(k[0])
            vs_out.append(v[0])
        x = layers.apply_norm(cfg, params["final_norm"], x)
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"])
        logits = _mask_pad_logits(cfg, x[0, -1] @ w)
        return logits, jnp.stack(ks_out), jnp.stack(vs_out)

    def prefill_chunk(self, chunk: List[int], offset: int, kbuf, vbuf):
        """Run `chunk` prompt tokens starting at `offset`. Returns
        (logits, k_chunk, v_chunk); logits stay on-device (async) — the
        caller argmaxes them only on a request's FINAL chunk, so
        intermediate chunks never force a host sync."""
        with span("exec.chunk.launch", tokens=len(chunk)):
            self._note_trace("chunk", (len(chunk), kbuf.shape[1]))
            return self._chunk_forward(
                self.params, jnp.asarray(chunk, jnp.int32), kbuf, vbuf,
                jnp.asarray(offset, jnp.int32),
                jnp.asarray(offset + len(chunk), jnp.int32))

    # ----------------------------------------------------------- fused step
    @functools.partial(jax.jit, static_argnums=(0, 18),
                       donate_argnums=(16, 17))
    @_program("serve_mixed")
    def _mixed_forward(self, params, tokens, q_pos, off, blk_dev, blk_host,
                       c_seg, c_qpos, c_kvlens, c_tables, c_tier, d_tables,
                       d_kvlens, sample_idx, is_chunk, dpool, hpool,
                       has_host):
        """ONE forward for a whole serving iteration: prefill-chunk tokens
        and decode tokens ride the same flat batch, so each layer's
        weights stream exactly once. Per layer: project QKV for all T
        tokens, scatter the new KV into the pool(s) at per-token
        (block, offset) slots, then attend straight over the pool — no
        dense prefix gather, no staging buffer. The flat batch is
        [chunk part (Tc rows, segment-padded to the query tile) |
        decode part (Rb rows, one per sequence)]: chunk rows go through
        the paged-prefill kernel, decode rows through the (unpadded)
        paged decode kernel — two attention calls but ONE weight stream,
        which is where the two-call executor paid twice.

        tokens/q_pos/off: (T,) flat batch (T = Tc + Rb); blk_dev/blk_host:
        (L, T) scatter targets (trash block for rows that don't write that
        tier). Chunk part: c_seg/c_qpos (Tc,), c_kvlens (Sc,), c_tables
        (L, Sc, MAXBc), c_tier (L, Sc) host-resident flags. Decode part:
        d_tables (L, Rb, MAXBd), d_kvlens (Rb,) cached tokens (attends
        ctx+1 after the in-step scatter). sample_idx: (Sb,) flat position
        each output row samples; is_chunk selects pad-vocab masking to
        mirror the two-call paths bit-for-bit. Returns
        (logits (Sb, V), dpool, hpool)."""
        cfg = self.cfg
        Tc = c_seg.shape[0]
        Rb = d_kvlens.shape[0]
        x = params["embed"][tokens][None]               # (1, T, d)
        positions = q_pos[None].astype(jnp.int32)       # (1, T)
        if cfg.pos_emb == "mrope":
            positions = jnp.broadcast_to(
                positions[None], (3, 1, tokens.shape[0]))
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = layers.apply_norm(cfg, lp["attn_norm"], x)
            q, k, v = layers.qkv_proj(cfg, lp["attn"], h)
            q = layers.apply_rope(cfg, q, positions)
            k = layers.apply_rope(cfg, k, positions)
            dpool = _scatter_tokens(dpool, blk_dev[l], off, k[0], v[0])
            if has_host:
                hpool = _scatter_tokens(hpool, blk_host[l], off, k[0], v[0])
            parts = []
            if Tc:
                parts.append(ops.paged_prefill(
                    q[0, :Tc], dpool, c_tables[l], c_seg, c_qpos, c_kvlens,
                    host_pool=hpool if has_host else None,
                    tier=c_tier[l] if has_host else None, tq=MIXED_TQ))
            if Rb:
                parts.append(ops.paged_attention(
                    q[0, Tc:], dpool, d_tables[l], d_kvlens + 1))
            o = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            x = x + layers.attn_out(cfg, lp["attn"], o[None])
            h = layers.apply_norm(cfg, lp["mlp_norm"], x)
            if cfg.family == "moe":
                from repro.models import moe as moe_mod
                f, _ = moe_mod.moe_ffn(cfg, lp["moe"], h, dropless=True)
            else:
                f = layers.mlp(cfg, lp["mlp"], h)
            x = x + f
        x = layers.apply_norm(cfg, params["final_norm"], x)
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"])
        feats = x[0][sample_idx]                        # (Sb, d)
        logits = feats @ w
        # chunk samples mask pad-vocab logits (as _chunk_forward does);
        # decode samples stay raw (as _paged_decode does)
        logits = jnp.where(is_chunk[:, None],
                           _mask_pad_logits(cfg, logits), logits)
        return logits, dpool, hpool

    def mixed_step(self, chunks: List[MixedChunk],
                   decodes: List[MixedDecode]) -> np.ndarray:
        """Run one fused iteration (`mixed_logits`) and return the
        (n_chunks + n_decodes,) argmax'd next tokens (chunk rows are only
        meaningful for a request's final chunk)."""
        logits = self.mixed_logits(chunks, decodes)
        with span("exec.mixed.wait"):
            return np.asarray(jnp.argmax(logits, axis=-1))

    def mixed_logits(self, chunks: List[MixedChunk],
                     decodes: List[MixedDecode]) -> jax.Array:
        """Run one fused iteration: all prefill chunks + the decode batch
        in one forward (one weight stream). Chunk KV and decode KV are
        scattered into the pools inside the step; attention reads the
        pools directly. Shapes are power-of-two bucketed (chunk rows Tc,
        chunk segments Sc, decode width Rb, output rows Sb; table widths
        round to 8 blocks) with padded rows writing the trash block, so
        steady state reuses compiled signatures. Returns the on-device
        (n_chunks + n_decodes, V) next-token logits: a chunk's row is
        its last token's."""
        with span("exec.mixed.prep", chunks=len(chunks),
                  rows=len(decodes)) as sp:
            TQ = MIXED_TQ
            BS = self.block_size
            L = self.cfg.n_layers
            n_c, n_d = len(chunks), len(decodes)
            assert n_c + n_d > 0, "mixed_step needs at least one segment"
            pads = [_round_up(len(c.tokens), TQ) for c in chunks]
            Tc = _bucket(sum(pads), TQ) if n_c else 0
            Sc = _bucket(n_c) if n_c else 0
            Rb = _bucket(n_d) if n_d else 0
            Sb = _bucket(n_c + n_d)
            T = Tc + Rb
            MAXBc = _round_up(max((len(c.tables[0]) for c in chunks),
                                  default=1), 8) if n_c else 0
            MAXBd = _round_up(max((len(d.tables[0]) for d in decodes),
                                  default=1), 8) if n_d else 0

            tokens = np.zeros(T, np.int32)
            q_pos = np.zeros(T, np.int32)
            off = np.zeros(T, np.int32)
            # padded rows scatter into the trash blocks
            blk_dev = np.full((L, T), self.num_device_blocks, np.int32)
            blk_host = np.full((L, T), self.num_host_blocks, np.int32)
            c_seg = np.full(Tc, max(Sc - 1, 0), np.int32)
            c_tables = np.zeros((L, Sc, MAXBc), np.int32)
            c_tier = np.zeros((L, Sc), bool)
            c_kvlens = np.zeros(Sc, np.int32)
            d_tables = np.full((L, Rb, MAXBd), self.num_device_blocks,
                               np.int32)
            d_kvlens = np.zeros(Rb, np.int32)
            sample_idx = np.zeros(Sb, np.int32)
            is_chunk = np.zeros(Sb, bool)

            t0 = 0
            for i, c in enumerate(chunks):
                C = len(c.tokens)
                tokens[t0:t0 + C] = c.tokens
                q_pos[t0:t0 + pads[i]] = c.offset + np.arange(pads[i])
                c_seg[t0:t0 + pads[i]] = i
                pos = c.offset + np.arange(C)
                off[t0:t0 + C] = pos % BS
                nb = len(c.tables[0])
                for l in range(L):
                    lblk = np.asarray(c.tables[l], np.int32)
                    c_tables[l, i, :nb] = lblk
                    c_tier[l, i] = c.tiers[l]
                    dst = blk_host if c.tiers[l] else blk_dev
                    dst[l, t0:t0 + C] = lblk[pos // BS]
                c_kvlens[i] = c.offset + C
                sample_idx[i] = t0 + C - 1
                is_chunk[i] = True
                t0 += pads[i]
            # chunk-part tail tiles: contiguous positions (a Pallas query
            # tile's base + row arithmetic must stay valid); they map to
            # the last chunk segment slot (a kv_len=0 dummy when Sc > n_c),
            # write only trash, and their outputs are discarded
            q_pos[t0:Tc] = np.arange(Tc - t0)
            for j, d in enumerate(decodes):
                t = Tc + j
                tokens[t] = d.token
                q_pos[t] = d.ctx
                off[t] = d.ctx % BS
                nb = len(d.tables[0])
                for l in range(L):
                    d_tables[l, j, :nb] = d.tables[l]
                    blk_dev[l, t] = d.tables[l][d.ctx // BS]
                d_kvlens[j] = d.ctx
                sample_idx[n_c + j] = t
            has_host = bool(c_tier.any())
            self._note_trace("mixed",
                             (Tc, Sc, Rb, Sb, MAXBc, MAXBd, has_host))
            sp.set_metadata(tokens_padded=T, rows_padded=Rb)
        with span("exec.mixed.launch"):
            logits, self.device_pool, self.host_pool = self._mixed_forward(
                self.params, jnp.asarray(tokens), jnp.asarray(q_pos),
                jnp.asarray(off), jnp.asarray(blk_dev),
                jnp.asarray(blk_host),
                jnp.asarray(c_seg), jnp.asarray(q_pos[:Tc]),
                jnp.asarray(c_kvlens), jnp.asarray(c_tables),
                jnp.asarray(c_tier), jnp.asarray(d_tables),
                jnp.asarray(d_kvlens), jnp.asarray(sample_idx),
                jnp.asarray(is_chunk), self.device_pool, self.host_pool,
                has_host)
            return logits[:n_c + n_d]

    # --------------------------------------------------------------- decode
    @_program("serve_decode")
    def _paged_decode(self, params, tokens, tables, kv_lens, dpool):
        """tokens: (R,) int32; tables: (L, R, MAXB) device block ids;
        kv_lens: (R,) tokens already cached. Returns (logits, dpool)."""
        cfg = self.cfg
        BS = self.block_size
        R = tokens.shape[0]
        x = params["embed"][tokens][:, None]  # (R,1,d)
        positions = kv_lens[:, None]  # new token's absolute position
        if cfg.pos_emb == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, R, 1))
        r_idx = jnp.arange(R)
        cur_block = kv_lens // BS
        cur_off = kv_lens % BS
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = layers.apply_norm(cfg, lp["attn_norm"], x)
            q, k, v = layers.decode_self_attention(
                cfg, lp["attn"], h, None, None, None, positions)
            # write the new token's KV into its block, in place
            blk = tables[l][r_idx, cur_block]  # (R,)
            dpool = ops.paged_kv_write(dpool, blk, cur_off, k[:, 0], v[:, 0])
            o = ops.paged_attention(q[:, 0], dpool, tables[l], kv_lens + 1)
            x = x + layers.attn_out(cfg, lp["attn"], o[:, None])
            h = layers.apply_norm(cfg, lp["mlp_norm"], x)
            if cfg.family == "moe":
                from repro.models import moe as moe_mod
                f, _ = moe_mod.moe_ffn(cfg, lp["moe"], h, dropless=True)
            else:
                f = layers.mlp(cfg, lp["mlp"], h)
            x = x + f
        x = layers.apply_norm(cfg, params["final_norm"], x)
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"])
        return (x[:, 0] @ w), dpool

    def decode(self, tokens: List[int], tables: np.ndarray,
               kv_lens: List[int]) -> List[int]:
        """One decode iteration (`decode_logits`); returns the R argmax'd
        next tokens."""
        logits = self.decode_logits(tokens, tables, kv_lens)
        with span("exec.decode.wait"):
            return np.asarray(jnp.argmax(logits, axis=-1)).tolist()

    def decode_logits(self, tokens: List[int], tables: np.ndarray,
                      kv_lens: List[int]) -> jax.Array:
        """One decode iteration. tables: (L, R, MAXB) int32 into the DEVICE
        pool (caller guarantees residency). The batch width R is padded to
        a power-of-two bucket and the table width MAXB to 8-block
        granularity (pow2 doubling would waste up to 2x gather traffic on
        the ref backend; 8 blocks bounds the waste while retracing at most
        once per 8 blocks of context growth) — padded rows carry
        trash-block tables (kv_len 0), so novel batch shapes reuse
        compiled signatures instead of retracing mid-serving. Returns the
        on-device (R, V) next-token logits."""
        R = len(tokens)
        L, _, maxb = tables.shape
        Rb = _bucket(R)
        MAXBb = _round_up(max(maxb, 1), 8)
        with span("exec.decode.prep", rows=R, rows_padded=Rb,
                  slots_padded=L * Rb * MAXBb):
            self._note_trace("decode", (Rb, MAXBb))
            toks = np.zeros(Rb, np.int32)
            toks[:R] = tokens
            lens = np.zeros(Rb, np.int32)
            lens[:R] = kv_lens
            tab = np.full((L, Rb, MAXBb), self.num_device_blocks, np.int32)
            tab[:, :R, :maxb] = tables
            # the in-place KV write's contract (kv_write.py): in every
            # layer each row writes a block of its own, or the trash block
            cur = np.sort(tab[:, np.arange(R), lens[:R] // self.block_size],
                          axis=1)
            assert ((cur[:, 1:] != cur[:, :-1])
                    | (cur[:, 1:] == self.num_device_blocks)).all(), \
                "decode rows share a current block"
        with span("exec.decode.launch"):
            logits, self.device_pool = self._decode_fn(
                self.params, jnp.asarray(toks), jnp.asarray(tab),
                jnp.asarray(lens), self.device_pool)
            return logits[:R]
