"""Decoder-only LM over block stacks (dense / MoE / SSM / hybrid / VLM).

Layer stacking: the model scans over *periods* (``blocks.block_kinds``); a
uniform arch has period length 1 (pure ``lax.scan`` over all layers — keeps
HLO size O(1) in depth so 512-device SPMD compiles stay tractable); Jamba
unrolls its 8-layer period inside a scan over 9 periods.

API (all pure):
  init(key, cfg) -> params
  forward(params, cfg, tokens, embeds=None) -> (logits [B,S,V], aux)
  init_state(cfg, batch, max_len) -> LMState
  prefill(params, cfg, tokens, state, embeds=None) -> (last_logits [B,V], LMState)
  decode(params, cfg, tokens [B,1], state) -> (logits [B,V], LMState)
    (the state's caches updated in place; see ``decode``)

``prefill`` is *suffix* prefill whenever ``state.pos > 0``: positions
``[0, state.pos)`` of the caches are treated as reused context state (the
paper's technique) and are not recomputed.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks, layers
from repro.models.common import KeyGen, Params, init_stacked, resolve_dtype


class LMState(NamedTuple):
    """Decode/prefill context state ("ContextState" in DESIGN.md)."""

    pos: jax.Array  # [B] — tokens already in the caches
    caches: Tuple[blocks.BlockCache, ...]  # one per period position, stacked over periods


def _layout(cfg: ArchConfig):
    kinds = blocks.block_kinds(cfg)
    assert cfg.n_layers % len(kinds) == 0, (cfg.name, cfg.n_layers, len(kinds))
    return kinds, cfg.n_layers // len(kinds)


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #
def init(key: jax.Array, cfg: ArchConfig) -> Params:
    kinds, n_periods = _layout(cfg)
    kg = KeyGen(key)
    layer_stacks = [
        init_stacked(kg(), n_periods, lambda k, kind=kind: blocks.init_block(k, cfg, kind))
        for kind in kinds
    ]
    return {
        "embed": layers.init_embedding(kg(), cfg),
        "layers": layer_stacks,
        "final_norm": layers.init_norm(cfg),
    }


def init_state(cfg: ArchConfig, batch: int, max_len: int, dtype=None) -> LMState:
    kinds, n_periods = _layout(cfg)

    def stacked(kind):
        one = blocks.init_block_cache(cfg, kind, batch, max_len, dtype)
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros((n_periods,) + l.shape, l.dtype), one
        )

    return LMState(
        pos=jnp.zeros((batch,), jnp.int32), caches=tuple(stacked(k) for k in kinds)
    )


# --------------------------------------------------------------------------- #
# Embedding (VLM stub frontends prepend precomputed patch embeddings)
# --------------------------------------------------------------------------- #
def _embed_inputs(
    params: Params, cfg: ArchConfig, tokens: Optional[jax.Array], embeds: Optional[jax.Array]
) -> jax.Array:
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(resolve_dtype(cfg.dtype)))
    if tokens is not None:
        parts.append(layers.embed_tokens(params["embed"], cfg, tokens))
    assert parts, "need tokens and/or embeds"
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


# --------------------------------------------------------------------------- #
# Training forward (no cache)
# --------------------------------------------------------------------------- #
def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    kinds, _ = _layout(cfg)
    x = _embed_inputs(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def period_fn(x, layer_params):
        aux = jnp.float32(0.0)
        for i, kind in enumerate(kinds):
            x, a = blocks.forward(layer_params[i], cfg, kind, x, positions=positions)
            aux = aux + a
        return x, aux

    x, auxes = jax.lax.scan(
        _remat(cfg, period_fn), x, tuple(params["layers"]), unroll=cfg.scan_unroll
    )
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)
    return logits, jnp.sum(auxes)


# --------------------------------------------------------------------------- #
# Prefill (full when state.pos == 0; suffix when state.pos > 0)
# --------------------------------------------------------------------------- #
def prefill(
    params: Params,
    cfg: ArchConfig,
    tokens: Optional[jax.Array],
    state: LMState,
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, LMState]:
    kinds, _ = _layout(cfg)
    x = _embed_inputs(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    offset = state.pos

    def period_fn(x, per):
        layer_params, caches = per
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c, _ = blocks.prefill(layer_params[i], cfg, kind, x, caches[i], offset)
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        _remat(cfg, period_fn), x, (tuple(params["layers"]), state.caches),
        unroll=cfg.scan_unroll,
    )
    x = layers.apply_norm(params["final_norm"], cfg, x[:, -1:])
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, LMState(pos=offset + S, caches=new_caches)


# --------------------------------------------------------------------------- #
# Packed ragged prefill (many requests, one launch) — attention archs only
# --------------------------------------------------------------------------- #
def prefill_packed(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # [1, Sq] new tokens of every segment, concatenated
    caches: Tuple[blocks.BlockCache, ...],  # packed buffers (paged.init_packed_caches)
    *,
    q_pos: jax.Array,  # [1, Sq]
    q_seg: jax.Array,  # [1, Sq]
    q_rows: jax.Array,  # [1, Sq]
    kv_pos: jax.Array,  # [1, Skv]
    kv_seg: jax.Array,  # [1, Skv]
    last_idx: jax.Array,  # [n] q index of each segment's last token
) -> Tuple[jax.Array, Tuple[blocks.BlockCache, ...]]:
    """Suffix-prefill of several requests as ONE packed sequence.

    Everything outside attention is positionwise, so packing is transparent
    to norms/MLP/MoE; attention isolates segments via ``q_seg``/``kv_seg``
    (see ``attention.prefill_packed``).  Returns per-segment last-token
    logits ``[n, V]`` (rows of ``last_idx``) and the updated packed caches,
    from which the caller scatters each segment back into its batch slot
    (``kvcache.paged.packed_to_artifact``).
    """
    kinds, _ = _layout(cfg)
    assert all(k.mixer == "a" for k in kinds), (
        "packed prefill requires attention-only stacks", cfg.name)
    x = _embed_inputs(params, cfg, tokens, None)

    def period_fn(x, per):
        layer_params, caches_ = per
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c, _ = blocks.prefill_packed(
                layer_params[i], cfg, kind, x, caches_[i],
                q_pos=q_pos, q_seg=q_seg, q_rows=q_rows,
                kv_pos=kv_pos, kv_seg=kv_seg,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        _remat(cfg, period_fn), x, (tuple(params["layers"]), caches),
        unroll=cfg.scan_unroll,
    )
    x = jnp.take_along_axis(x, last_idx.astype(jnp.int32)[None, :, None], axis=1)
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[0]  # [n, V]
    return logits, new_caches


# --------------------------------------------------------------------------- #
# Fused selective-recompute prefill (non-prefix chunk reuse) — attention only
# --------------------------------------------------------------------------- #
def prefill_fused(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # [1, Sq] the recompute tokens, in position order
    caches: Tuple[blocks.BlockCache, ...],  # assembled buffers (fusion.build_fused_caches)
    *,
    q_pos: jax.Array,  # [1, Sq] absolute positions (gappy; padding -2^30)
    q_rows: jax.Array,  # [1, Sq] buffer row per token (padding -> scratch)
    kv_pos: jax.Array,  # [1, Skv] row positions (-1 invalid)
    last_idx: jax.Array,  # [1] q index of the final (prompt) token
) -> Tuple[jax.Array, Tuple[blocks.BlockCache, ...]]:
    """Selective-recompute prefill over a chunk-composite KV assembly.

    The CacheBlend-style execute path: reused chunk spans sit preloaded in
    ``caches`` and only the selected r-fraction of tokens (plus every prompt
    token) flows through the layer stack, each attending the full assembled
    buffer at its absolute position.  Everything outside attention is
    positionwise, so the gappy token subset is transparent to norms/MLP/MoE;
    attention semantics live in ``attention.prefill_fused``.  Returns the
    last-token logits ``[1, V]`` and the updated buffers, from which the
    caller slices the full context+prompt state (rows ``[0, total)``) for
    slot installation or pool landing.  At ``recompute_frac=1.0`` the token
    set is the whole sequence and the result is bit-identical to ``prefill``
    (tests/test_fusion.py).
    """
    kinds, _ = _layout(cfg)
    assert all(k.mixer == "a" for k in kinds), (
        "fused prefill requires attention-only stacks", cfg.name)
    x = _embed_inputs(params, cfg, tokens, None)

    def period_fn(x, per):
        layer_params, caches_ = per
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c, _ = blocks.prefill_fused(
                layer_params[i], cfg, kind, x, caches_[i],
                q_pos=q_pos, q_rows=q_rows, kv_pos=kv_pos,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        _remat(cfg, period_fn), x, (tuple(params["layers"]), caches),
        unroll=cfg.scan_unroll,
    )
    x = jnp.take_along_axis(x, last_idx.astype(jnp.int32)[None, :, None], axis=1)
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[0]  # [1, V]
    return logits, new_caches


# --------------------------------------------------------------------------- #
# Decode (one token per sequence)
# --------------------------------------------------------------------------- #
def decode(
    params: Params, cfg: ArchConfig, tokens: jax.Array, state: LMState
) -> Tuple[jax.Array, LMState]:
    """One token per sequence.  The stacked caches ride the layer loop's
    carry and the layer params are its ``xs``: each layer writes its B new
    K/V rows into the carried ``[n_periods, B, L, KV, hd]`` buffers at
    ``(period, b, pos[b])`` and the attention reads its layer where it lies,
    so under jit the buffers are updated in place (no per-layer slice or
    write-back; donate the state to reuse its buffers across steps).  SSM
    state is indexed out of the carry and written back per layer."""
    kinds, n_periods = _layout(cfg)
    x = _embed_inputs(params, cfg, tokens, None)
    pos = state.pos

    def period_fn(carry, per):
        x, caches = carry
        layer_params, i = per
        caches = list(caches)
        for j, kind in enumerate(kinds):
            x, caches[j] = blocks.decode(layer_params[j], cfg, kind, x, caches[j], i, pos)
        return (x, tuple(caches)), None

    (x, caches), _ = jax.lax.scan(
        period_fn, (x, state.caches),
        (tuple(params["layers"]), jnp.arange(n_periods, dtype=jnp.int32)),
        unroll=cfg.scan_unroll,
    )
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, LMState(pos=pos + 1, caches=caches)


# --------------------------------------------------------------------------- #
# Paged decode (one token per sequence over the shared KV block pool)
# --------------------------------------------------------------------------- #
def decode_paged(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # [B, 1]
    caches: Tuple[blocks.BlockCache, ...],  # pool buffers (paged.init_pool_caches)
    *,
    block_table: jax.Array,  # [B, nb] int32 pool-block ids per sequence block
    pos: jax.Array,  # [B] int32 — cached length per slot (0-padded tables for
    # freed slots route their writes to the reserved dump block)
    block: int = 128,
) -> Tuple[jax.Array, Tuple[blocks.BlockCache, ...]]:
    """``decode`` against the shared block pool instead of per-slot dense
    caches: every layer's attention gathers exactly the live blocks each
    slot's table names (``attention.decode_paged``).  Positions/tables are
    host-managed by the caller (the serving engine), so only the pool
    buffers flow through: returns (logits [B, V], updated caches) —
    bit-identical logits to ``decode`` (tests/test_paged_decode.py)."""
    kinds, _ = _layout(cfg)
    assert all(k.mixer == "a" for k in kinds), (
        "paged decode requires attention-only stacks", cfg.name)
    x = _embed_inputs(params, cfg, tokens, None)

    def period_fn(x, per):
        layer_params, caches_ = per
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c = blocks.decode_paged(
                layer_params[i], cfg, kind, x, caches_[i], block_table, pos,
                block=block,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        period_fn, x, (tuple(params["layers"]), caches), unroll=cfg.scan_unroll
    )
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, new_caches


# --------------------------------------------------------------------------- #
# Chunked prefill (mixed prefill-chunk + decode rows over the block pool)
# --------------------------------------------------------------------------- #
def prefill_chunked(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # [B, C] — up to C new tokens per slot (0 on padding)
    caches: Tuple[blocks.BlockCache, ...],  # pool buffers (paged.init_pool_caches)
    *,
    block_table: jax.Array,  # [B, nb] int32 pool-block ids per sequence block
    q_pos: jax.Array,  # [B, C] int32 token positions (-2^30 = padding)
    last_idx: jax.Array,  # [B] chunk index of each row's last valid token
    block: int = 128,
) -> Tuple[jax.Array, Tuple[blocks.BlockCache, ...]]:
    """The unified continuous-batching step: ONE launch over the shared
    block pool whose rows mix prefill chunks (up to ``C`` new suffix tokens
    each), decode rows (1 token at the live length) and idle rows (all
    padding).  Every valid token's KV lands in the pool blocks its slot's
    table names (``attention.prefill_chunked``), then attends causally at
    its absolute position — per-row numerics are bit-identical to the
    legacy suffix-prefill / paged-decode launches.  Returns per-row logits
    ``[B, V]`` gathered at ``last_idx`` (meaningful only for rows whose
    chunk completes a prefill or carries a decode token) and the updated
    pool buffers.  Static shapes ([B, C] tokens, [B, nb] tables) make the
    launch compile once per (C, nb) bucket — zero steady-state recompiles.
    """
    kinds, _ = _layout(cfg)
    assert all(k.mixer == "a" for k in kinds), (
        "chunked prefill requires attention-only stacks", cfg.name)
    x = _embed_inputs(params, cfg, tokens, None)

    def period_fn(x, per):
        layer_params, caches_ = per
        new_caches = []
        for i, kind in enumerate(kinds):
            x, c, _ = blocks.prefill_chunked(
                layer_params[i], cfg, kind, x, caches_[i], block_table, q_pos,
                block=block,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        _remat(cfg, period_fn), x, (tuple(params["layers"]), caches),
        unroll=cfg.scan_unroll,
    )
    x = jnp.take_along_axis(x, last_idx.astype(jnp.int32)[:, None, None], axis=1)
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]  # [B, V]
    return logits, new_caches


# --------------------------------------------------------------------------- #
# Loss
# --------------------------------------------------------------------------- #
def cross_entropy(
    logits: jax.Array,  # [B, S, V] (activation dtype; upcast internally)
    labels: jax.Array,  # [B, S] int32
    mask: Optional[jax.Array] = None,  # [B, S] float/bool
) -> jax.Array:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)  # [B, S]
    label_logit = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    nll = lse - label_logit
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
