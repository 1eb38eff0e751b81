"""Decoder blocks: (attention | mamba) mixer + optional (MLP | MoE) FFN.

A block is described by a static :class:`BlockKind`; parameters are nested
dicts so homogeneous stacks scan cleanly and hybrid periods unroll.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention, layers, moe, ssm
from repro.models.common import KeyGen, Params


class BlockKind(NamedTuple):
    mixer: str  # "a" (attention) | "m" (mamba)
    ffn: str  # "mlp" | "moe" | "none"


def block_kinds(cfg: ArchConfig) -> Tuple[BlockKind, ...]:
    """Static per-layer block kinds for one scan period.

    * uniform families (dense/moe/ssm/vlm): period length 1;
    * hybrid (Jamba): the full ``hybrid_period`` with MoE on layers where
      ``idx % moe.every == moe.offset``.
    """
    if cfg.family == "hybrid":
        assert cfg.hybrid_period is not None and cfg.moe is not None
        kinds = []
        for i, mixer in enumerate(cfg.hybrid_period):
            is_moe = i % cfg.moe.every == cfg.moe.offset
            kinds.append(BlockKind(mixer, "moe" if is_moe else "mlp"))
        return tuple(kinds)
    if cfg.family == "ssm":
        return (BlockKind("m", "none" if cfg.d_ff == 0 else "mlp"),)
    if cfg.family == "moe":
        assert cfg.moe is not None and cfg.moe.every == 1, (
            "uniform scan requires MoE on every layer; use family='hybrid' otherwise"
        )
        return (BlockKind("a", "moe"),)
    return (BlockKind("a", "mlp"),)


def init_block(key: jax.Array, cfg: ArchConfig, kind: BlockKind) -> Params:
    kg = KeyGen(key)
    p: Params = {"norm1": layers.init_norm(cfg)}
    if kind.mixer == "a":
        p["attn"] = attention.init_attention(kg(), cfg)
    else:
        p["mamba"] = ssm.init_mamba(kg(), cfg)
    if kind.ffn != "none":
        p["norm2"] = layers.init_norm(cfg)
        p["ffn"] = (
            moe.init_moe(kg(), cfg) if kind.ffn == "moe" else layers.init_mlp(kg(), cfg)
        )
    return p


def _apply_ffn(p: Params, cfg: ArchConfig, kind: BlockKind, x: jax.Array):
    if kind.ffn == "none":
        return x, jnp.float32(0.0)
    h = layers.apply_norm(p["norm2"], cfg, x)
    if kind.ffn == "moe":
        out, aux = moe.apply_moe(p["ffn"], cfg, h)
        return x + out, aux
    return x + layers.apply_mlp(p["ffn"], cfg, h), jnp.float32(0.0)


# --------------------------------------------------------------------------- #
# Training forward (no cache)
# --------------------------------------------------------------------------- #
def forward(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    h = layers.apply_norm(p["norm1"], cfg, x)
    if kind.mixer == "a":
        x = x + attention.forward(p["attn"], cfg, h, positions=positions)
    else:
        out, _ = ssm.forward(p["mamba"], cfg, h)
        x = x + out
    return _apply_ffn(p, cfg, kind, x)


# --------------------------------------------------------------------------- #
# Prefill with per-layer cache slices; decode against the stacked caches
# --------------------------------------------------------------------------- #
class BlockCache(NamedTuple):
    """Union cache for one layer; unused member is None (static per kind)."""

    attn: Optional[attention.KVCache]
    mamba: Optional[ssm.MambaState]


def init_block_cache(
    cfg: ArchConfig, kind: BlockKind, batch: int, max_len: int, dtype=None
) -> BlockCache:
    if kind.mixer == "a":
        return BlockCache(attention.init_kv_cache(cfg, batch, max_len, dtype), None)
    return BlockCache(None, ssm.init_mamba_state(cfg, batch, dtype))


def prefill(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,
    cache: BlockCache,
    offset: jax.Array,  # [B]
) -> Tuple[jax.Array, BlockCache, jax.Array]:
    h = layers.apply_norm(p["norm1"], cfg, x)
    if kind.mixer == "a":
        out, kv = attention.prefill(p["attn"], cfg, h, cache.attn, offset)
        cache = BlockCache(kv, None)
    else:
        out, st = ssm.forward(p["mamba"], cfg, h, state=cache.mamba)
        cache = BlockCache(None, st)
    x = x + out
    x, aux = _apply_ffn(p, cfg, kind, x)
    return x, cache, aux


def prefill_packed(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,  # [1, Sq, D]
    cache: BlockCache,  # packed attention KV buffer (mixer must be "a")
    *,
    q_pos: jax.Array,
    q_seg: jax.Array,
    q_rows: jax.Array,
    kv_pos: jax.Array,
    kv_seg: jax.Array,
) -> Tuple[jax.Array, BlockCache, jax.Array]:
    """Packed ragged prefill of one block — attention mixers only (SSM state
    mixes along the sequence, so SSM/hybrid archs cannot be packed)."""
    assert kind.mixer == "a", "packed prefill requires an attention mixer"
    h = layers.apply_norm(p["norm1"], cfg, x)
    out, kv = attention.prefill_packed(
        p["attn"], cfg, h, cache.attn,
        q_pos=q_pos, q_seg=q_seg, q_rows=q_rows, kv_pos=kv_pos, kv_seg=kv_seg,
    )
    x = x + out
    x, aux = _apply_ffn(p, cfg, kind, x)
    return x, BlockCache(kv, None), aux


def prefill_fused(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,  # [1, Sq, D] — recompute tokens only
    cache: BlockCache,  # assembled attention KV buffer (mixer must be "a")
    *,
    q_pos: jax.Array,
    q_rows: jax.Array,
    kv_pos: jax.Array,
) -> Tuple[jax.Array, BlockCache, jax.Array]:
    """Selective-recompute fused prefill of one block — attention mixers
    only (SSM state mixes along the sequence, so chunk-composite reuse
    cannot skip tokens there)."""
    assert kind.mixer == "a", "fused prefill requires an attention mixer"
    h = layers.apply_norm(p["norm1"], cfg, x)
    out, kv = attention.prefill_fused(
        p["attn"], cfg, h, cache.attn, q_pos=q_pos, q_rows=q_rows, kv_pos=kv_pos
    )
    x = x + out
    x, aux = _apply_ffn(p, cfg, kind, x)
    return x, BlockCache(kv, None), aux


def prefill_chunked(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,  # [B, C, D]
    cache: BlockCache,  # shared block-pool KV buffer (mixer must be "a")
    block_table: jax.Array,  # [B, nb]
    q_pos: jax.Array,  # [B, C]
    *,
    block: int,
) -> Tuple[jax.Array, BlockCache, jax.Array]:
    """Chunked prefill of one block over the pool — attention mixers only
    (SSM state mixes along the sequence, so chunk interleaving cannot skip
    ahead there; those archs keep the legacy admit-then-decode path)."""
    assert kind.mixer == "a", "chunked prefill requires an attention mixer"
    h = layers.apply_norm(p["norm1"], cfg, x)
    out, kv = attention.prefill_chunked(
        p["attn"], cfg, h, cache.attn, block_table, q_pos, block=block
    )
    x = x + out
    x, aux = _apply_ffn(p, cfg, kind, x)
    return x, BlockCache(kv, None), aux


def decode_paged(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,  # [B, 1, D]
    cache: BlockCache,  # shared block-pool KV buffer (mixer must be "a")
    block_table: jax.Array,  # [B, nb]
    pos: jax.Array,  # [B]
    *,
    block: int,
) -> Tuple[jax.Array, BlockCache]:
    """Paged decode of one block — attention mixers only (SSM state is O(1)
    per slot and gains nothing from paging; those archs keep dense decode)."""
    assert kind.mixer == "a", "paged decode requires an attention mixer"
    h = layers.apply_norm(p["norm1"], cfg, x)
    out, kv = attention.decode_paged(
        p["attn"], cfg, h, cache.attn, block_table, pos, block=block
    )
    x = x + out
    x, _ = _apply_ffn(p, cfg, kind, x)
    return x, BlockCache(kv, None)


def decode(
    p: Params,
    cfg: ArchConfig,
    kind: BlockKind,
    x: jax.Array,
    cache: BlockCache,  # every period's cache of this kind, stacked
    layer: jax.Array,  # int32 scalar — this block's period index
    pos: jax.Array,  # [B]
) -> Tuple[jax.Array, BlockCache]:
    """Decode one block against the stacked cache, returning the stack with
    this block's entry updated: attention writes its new rows in place
    (``attention.decode``); the O(1) SSM state is indexed out and written
    back."""
    h = layers.apply_norm(p["norm1"], cfg, x)
    if kind.mixer == "a":
        out, kv = attention.decode(p["attn"], cfg, h, cache.attn, layer, pos)
        cache = BlockCache(kv, None)
    else:
        st = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache.mamba,
        )
        out, st = ssm.decode(p["mamba"], cfg, h, st)
        cache = BlockCache(None, jax.tree_util.tree_map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, layer, 0),
            cache.mamba, st,
        ))
    x = x + out
    x, _ = _apply_ffn(p, cfg, kind, x)
    return x, cache
