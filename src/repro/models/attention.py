"""Grouped-query self-attention (+ cross-attention) with a slotted KV cache.

Three call modes share one weight set:
  * ``forward``  — full training forward (no cache).
  * ``prefill``  — writes KV for ``S`` new tokens at ``offset`` into the cache
                   and attends causally over ``[0, offset+S)``.  With
                   ``offset > 0`` this is the paper's *suffix prefill*: the
                   reused context KV occupying ``[0, offset)`` is NOT
                   recomputed.
  * ``decode``   — one token per sequence against one layer of the
                   stacked cache, updated in place (ring-buffer indexing
                   for sliding-window attention).

Cache layout (TPU-native slotted dense cache, see DESIGN.md §3):
  k/v: [B, L_cache, KV_heads, head_dim]
where ``L_cache = min(max_len, window)`` for SWA archs; a decode state
stacks it over layers, ``[n_layers, B, L_cache, KV_heads, head_dim]``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import common
from repro.models.common import KeyGen, Params
from repro.models.layers import apply_rope


class KVCache(NamedTuple):
    """Per-layer slotted KV cache (a pytree leaf-pair)."""

    k: jax.Array  # [B, L_cache, KV, hd]
    v: jax.Array  # [B, L_cache, KV, hd]


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    dtype = dtype or common.resolve_dtype(cfg.dtype)
    shape = (batch, length, cfg.n_kv_heads, cfg.resolved_head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# --------------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------------- #
def init_attention(key: jax.Array, cfg: ArchConfig) -> Params:
    kg = KeyGen(key)
    pdtype = common.resolve_dtype(cfg.param_dtype)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p: Params = {
        "wq": common.dense_init(kg(), (D, H, hd), pdtype, fan_in=D),
        "wk": common.dense_init(kg(), (D, KV, hd), pdtype, fan_in=D),
        "wv": common.dense_init(kg(), (D, KV, hd), pdtype, fan_in=D),
        "wo": common.dense_init(kg(), (H, hd, D), pdtype, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), pdtype)
        p["bk"] = jnp.zeros((KV, hd), pdtype)
        p["bv"] = jnp.zeros((KV, hd), pdtype)
    return p


def _qkv(p: Params, cfg: ArchConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dke->bske", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dke->bske", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _out(p: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("bshe,hed->bsd", x, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------- #
# Training forward (no cache)
# --------------------------------------------------------------------------- #
def forward(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [B, S, D]
    *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,  # [B, S]
) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = ops.flash_attention(
        q, k, v, q_pos=positions, kv_pos=positions, causal=causal,
        window=cfg.sliding_window,
    )
    return _out(p, o)


# --------------------------------------------------------------------------- #
# Prefill (full or suffix) against a slotted cache
# --------------------------------------------------------------------------- #
def prefill(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [B, S, D] — the *new* (non-reused) tokens
    cache: KVCache,
    offset: jax.Array,  # [B] int32 — number of already-cached context tokens
) -> Tuple[jax.Array, KVCache]:
    B, S, _ = x.shape
    L = cache.k.shape[1]
    q, k_new, v_new = _qkv(p, cfg, x)
    positions = offset[:, None] + jnp.arange(S, dtype=jnp.int32)[None]  # [B, S]
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)

    if cfg.sliding_window and L == cfg.sliding_window:
        # Ring buffer (SWA). Queries early in the chunk need keys that later
        # writes would overwrite, so attend over [old ring ∪ new KV] and only
        # write each slot's LAST occurrence back into the ring.
        W = cfg.sliding_window
        old_pos = _ring_positions(offset, W, B)  # positions held before this call
        k_all = jnp.concatenate([cache.k, k_new], axis=1)
        v_all = jnp.concatenate([cache.v, v_new], axis=1)
        kv_pos_all = jnp.concatenate([old_pos, positions], axis=1)
        o = ops.flash_attention(
            q, k_all, v_all, q_pos=positions, kv_pos=kv_pos_all, causal=True, window=W
        )
        slots = positions % W
        write = positions >= (offset[:, None] + S - W)  # last occurrence per slot
        slots_eff = jnp.where(write, slots, W)  # dropped -> scratch row
        cache = KVCache(
            _scatter_rows_padded(cache.k, slots_eff, k_new),
            _scatter_rows_padded(cache.v, slots_eff, v_new),
        )
        return _out(p, o), cache
    else:
        # Contiguous write at [offset, offset+S).  Uniform offset uses a cheap
        # dynamic slice; ragged offsets fall back to a scatter.
        cache = KVCache(
            _write_rows(cache.k, offset, k_new), _write_rows(cache.v, offset, v_new)
        )
        idx = jnp.arange(L, dtype=jnp.int32)[None]
        kv_pos = jnp.where(idx < (offset[:, None] + S), idx, -1)  # [B, L]

    o = ops.flash_attention(
        q, cache.k, cache.v, q_pos=positions, kv_pos=kv_pos, causal=True,
        window=cfg.sliding_window,
    )
    return _out(p, o), cache


# --------------------------------------------------------------------------- #
# Packed ragged (suffix-)prefill: many requests, one kernel launch
# --------------------------------------------------------------------------- #
def prefill_packed(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [1, Sq, D] — new tokens of ALL segments, concatenated
    cache: KVCache,  # [1, Skv, KV, hd] packed buffer, reused prefixes preloaded
    *,
    q_pos: jax.Array,  # [1, Sq] segment-local positions of the new tokens
    q_seg: jax.Array,  # [1, Sq] segment id per query token (-1 = padding)
    q_rows: jax.Array,  # [1, Sq] packed-buffer row each new token's KV lands in
    kv_pos: jax.Array,  # [1, Skv] segment-local position per kv row (-1 invalid)
    kv_seg: jax.Array,  # [1, Skv] segment id per kv row
) -> Tuple[jax.Array, KVCache]:
    """Suffix-prefill of several requests in one attention call.

    ``cache`` is the *packed* KV buffer: each segment owns a contiguous row
    span holding [its reused context KV ++ its new KV], laid out by the
    caller (``kvcache.paged.PackLayout``).  New-token K/V are scattered to
    ``q_rows`` (padding tokens carry an out-of-range row and land on a
    dropped scratch row), then every query attends its own segment only
    (``q_seg == kv_seg``), causally at segment-local positions — numerically
    the same attention each request would run alone.
    """
    q, k_new, v_new = _qkv(p, cfg, x)
    if cfg.rope_theta is not None:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k_new = apply_rope(k_new, q_pos, cfg.rope_theta)
    cache = KVCache(
        _scatter_rows_padded(cache.k, q_rows, k_new),
        _scatter_rows_padded(cache.v, q_rows, v_new),
    )
    o = ops.packed_attention(
        q, cache.k, cache.v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg,
        kv_seg=kv_seg, causal=True, window=cfg.sliding_window,
    )
    return _out(p, o), cache


# --------------------------------------------------------------------------- #
# Fused selective-recompute prefill (CacheBlend-style non-prefix reuse)
# --------------------------------------------------------------------------- #
def prefill_fused(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [1, Sq, D] — ONLY the tokens chosen for recompute
    cache: KVCache,  # [1, Skv, KV, hd] assembled buffer, reused spans preloaded
    *,
    q_pos: jax.Array,  # [1, Sq] absolute positions of the recompute tokens
    q_rows: jax.Array,  # [1, Sq] buffer row each token's fresh KV lands in
    kv_pos: jax.Array,  # [1, Skv] row positions (-1 = invalid/padding)
) -> Tuple[jax.Array, KVCache]:
    """Selective-recompute prefill of one request over an assembled buffer.

    ``cache`` holds the context KV in query order, with reused chunk spans
    preloaded from storage (``kvcache.fusion.build_fused_caches``) and zeros
    at the recompute rows.  The recompute tokens — a gappy subset of
    positions, not a suffix — get fresh K/V scattered into their rows
    (padding tokens carry an out-of-range row and land on a dropped scratch
    row), then attend causally over the FULL buffer at their absolute
    positions (``ops.fused_prefill``).  At r=1.0 every row is overwritten
    and this is exactly ``prefill`` of the whole sequence.
    """
    q, k_new, v_new = _qkv(p, cfg, x)
    if cfg.rope_theta is not None:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k_new = apply_rope(k_new, q_pos, cfg.rope_theta)
    cache = KVCache(
        _scatter_rows_padded(cache.k, q_rows, k_new),
        _scatter_rows_padded(cache.v, q_rows, v_new),
    )
    o = ops.fused_prefill(
        q, cache.k, cache.v, q_pos=q_pos, kv_pos=kv_pos,
        window=cfg.sliding_window,
    )
    return _out(p, o), cache


# --------------------------------------------------------------------------- #
# Decode (one token)
# --------------------------------------------------------------------------- #
def decode(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [B, 1, D]
    cache: KVCache,  # k/v: [n_layers, B, L_cache, KV, hd] — every layer's cache
    layer: jax.Array,  # int32 scalar — this layer's index into ``cache``
    pos: jax.Array,  # [B] int32 — position of this token (== cached length)
) -> Tuple[jax.Array, KVCache]:
    """One token per sequence against layer ``layer`` of the stacked cache.

    The cache is updated in place: the B new K/V rows are scattered to
    ``(layer, b, pos[b])`` (``pos % window`` for a ring buffer), and the
    attention reads the layer where it lies (``ops.decode_attention``), so
    no per-layer slice of the cache is taken or written back.
    """
    B = x.shape[0]
    L = cache.k.shape[2]
    q, k_new, v_new = _qkv(p, cfg, x)
    positions = pos[:, None]  # [B, 1]
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)

    if cfg.sliding_window and L == cfg.sliding_window:
        rows = pos % cfg.sliding_window
        kv_pos = _ring_positions(pos + 1, L, B)
    else:
        rows = pos
        idx = jnp.arange(L, dtype=jnp.int32)[None]
        kv_pos = jnp.where(idx <= pos[:, None], idx, -1)
    cache = KVCache(
        _write_layer_rows(cache.k, layer, rows, k_new[:, 0]),
        _write_layer_rows(cache.v, layer, rows, v_new[:, 0]),
    )
    o = ops.decode_attention(
        q, cache.k, cache.v, layer=layer, q_pos=positions, kv_pos=kv_pos,
        window=cfg.sliding_window,
    )
    return _out(p, o), cache


# --------------------------------------------------------------------------- #
# Paged decode (one token per sequence against the shared block pool)
# --------------------------------------------------------------------------- #
def decode_paged(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [B, 1, D]
    pool: KVCache,  # k/v: [N_rows, KV, hd] — the SHARED block pool, flat rows
    block_table: jax.Array,  # [B, nb] int32 pool-block id per sequence block
    pos: jax.Array,  # [B] int32 — position of this token (== cached length)
    *,
    block: int,
) -> Tuple[jax.Array, KVCache]:
    """``decode`` over the paged layout: the new token's K/V rows scatter
    into the pool at ``table[pos // block] * block + pos % block`` and
    attention gathers each sequence's live blocks through its table
    (``ops.paged_decode``).  A slot whose table is zeroed (freed/inactive)
    writes onto the reserved dump block's rows — never into a block that may
    have been recycled to another sequence.  Numerics are bit-identical to
    ``decode`` against a slotted-dense cache (tests/test_paged_decode.py).
    """
    B = x.shape[0]
    q, k_new, v_new = _qkv(p, cfg, x)
    positions = pos[:, None]  # [B, 1]
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)

    blk = jnp.take_along_axis(
        block_table.astype(jnp.int32), (pos // block)[:, None], axis=1
    )[:, 0]
    rows = blk * block + pos % block  # [B] — dump rows when blk == 0
    pool = KVCache(
        pool.k.at[rows].set(k_new[:, 0]), pool.v.at[rows].set(v_new[:, 0])
    )
    o = ops.paged_decode(
        q, pool.k, pool.v, block_table=block_table, q_pos=positions,
        block=block, window=cfg.sliding_window,
    )
    return _out(p, o), pool


# --------------------------------------------------------------------------- #
# Chunked prefill (mixed prefill-chunk + decode rows over the block pool)
# --------------------------------------------------------------------------- #
def prefill_chunked(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # [B, C, D] — up to C new tokens per sequence
    pool: KVCache,  # k/v: [N_rows, KV, hd] — the SHARED block pool, flat rows
    block_table: jax.Array,  # [B, nb] int32 pool-block id per sequence block
    q_pos: jax.Array,  # [B, C] int32 token positions (-2^30 = padding)
    *,
    block: int,
) -> Tuple[jax.Array, KVCache]:
    """``decode_paged`` generalised to a chunk of up to ``C`` tokens per
    sequence — the unified continuous-batching step.  Each valid token's K/V
    rows scatter into the pool at ``table[pos // block] * block + pos %
    block``; padding tokens write onto the reserved dump block's row 0 (their
    rope positions are clamped to 0 first, so only garbage lands there and
    dump rows are never attended — positions exceed every valid query).  One
    launch mixes decode rows (1 valid token), prefill-chunk rows (many) and
    idle rows (none); numerics per row are bit-identical to dense suffix
    prefill / ``decode`` (tests/test_chunked_prefill.py).
    """
    B, C, _ = x.shape
    q, k_new, v_new = _qkv(p, cfg, x)
    valid = q_pos >= 0  # [B, C]
    positions = jnp.where(valid, q_pos, 0).astype(jnp.int32)
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)

    blk = jnp.take_along_axis(
        block_table.astype(jnp.int32), positions // block, axis=1
    )  # [B, C]
    rows = jnp.where(valid, blk * block + positions % block, 0)  # 0 = dump row
    KVh, hd = pool.k.shape[1], pool.k.shape[2]
    rows_flat = rows.reshape(B * C)
    pool = KVCache(
        pool.k.at[rows_flat].set(k_new.reshape(B * C, KVh, hd)),
        pool.v.at[rows_flat].set(v_new.reshape(B * C, KVh, hd)),
    )
    o = ops.chunked_prefill(
        q, pool.k, pool.v, block_table=block_table, q_pos=q_pos,
        block=block, window=cfg.sliding_window,
    )
    return _out(p, o), pool


# --------------------------------------------------------------------------- #
# Cross-attention (Whisper decoder): KV computed once from encoder output
# --------------------------------------------------------------------------- #
def init_cross_attention(key: jax.Array, cfg: ArchConfig) -> Params:
    return init_attention(key, cfg)


def cross_kv(p: Params, cfg: ArchConfig, enc_out: jax.Array) -> KVCache:
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dke->bske", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dke->bske", enc_out, p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return KVCache(k, v)


def cross_attend(p: Params, cfg: ArchConfig, x: jax.Array, ckv: KVCache) -> jax.Array:
    B, S, _ = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    Skv = ckv.k.shape[1]
    q_pos = jnp.zeros((B, S), jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32)[None], (B, Skv))
    o = ops.flash_attention(q, ckv.k, ckv.v, q_pos=q_pos, kv_pos=kv_pos, causal=False)
    return _out(p, o)


# --------------------------------------------------------------------------- #
# Cache write helpers
# --------------------------------------------------------------------------- #
def _write_rows(cache: jax.Array, offset: jax.Array, new: jax.Array) -> jax.Array:
    """Write ``new`` [B,S,...] into ``cache`` [B,L,...] at row ``offset[b]``."""
    B, S = new.shape[0], new.shape[1]

    def per_seq(c, o, n):
        return jax.lax.dynamic_update_slice(c, n, (o,) + (0,) * (c.ndim - 1))

    return jax.vmap(per_seq)(cache, offset.astype(jnp.int32), new)


def _write_layer_rows(
    cache: jax.Array, layer: jax.Array, rows: jax.Array, new: jax.Array
) -> jax.Array:
    """Write ``new`` [B, ...] into the stacked ``cache`` [n_layers, B, L, ...]
    at ``(layer, b, rows[b])``: one scatter of B rows, in place under jit."""
    B = new.shape[0]
    return cache.at[layer, jnp.arange(B), rows.astype(jnp.int32)].set(
        new.astype(cache.dtype)
    )


def _scatter_rows(cache: jax.Array, slots: jax.Array, new: jax.Array) -> jax.Array:
    """Scatter ``new`` [B,S,...] rows into per-sequence slots [B,S]."""

    def per_seq(c, s, n):
        return c.at[s].set(n)

    return jax.vmap(per_seq)(cache, slots.astype(jnp.int32), new)


def _scatter_rows_padded(cache: jax.Array, slots: jax.Array, new: jax.Array) -> jax.Array:
    """Scatter with a scratch row at index L (rows sent there are dropped) —
    used to suppress duplicate ring-buffer writes without data-dependent
    shapes."""
    L = cache.shape[1]
    pad = jnp.zeros_like(cache[:, :1])
    padded = jnp.concatenate([cache, pad], axis=1)
    return _scatter_rows(padded, slots, new)[:, :L]


def _ring_positions(length: jax.Array, window: int, batch: int) -> jax.Array:
    """Absolute position held by each ring slot given ``length`` tokens seen.

    Slot j holds the largest position p < length with p % window == j
    (or -1 if no token ever landed there).
    """
    j = jnp.arange(window, dtype=jnp.int32)[None]  # [1, W]
    ln = length.astype(jnp.int32)[:, None]  # [B, 1]
    p = ln - 1 - ((ln - 1 - j) % window)
    return jnp.where((p >= 0) & (ln > 0), p, -1)
