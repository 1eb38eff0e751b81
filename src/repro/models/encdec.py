"""Whisper-style encoder–decoder transformer.

Per the assignment the audio frontend (mel conv stem) is a STUB:
``input_specs()`` provides precomputed frame embeddings ``[B, S_enc, D]``.
LayerNorm + GELU MLP + sinusoidal (encoder) / trained (decoder) absolute
positions, per the Whisper architecture (arXiv:2212.04356).

Reusable context state for the paper's technique (DESIGN.md §6): the encoder
output and the decoder's *cross*-attention KV of the audio context; decoder
self-attention KV is per-request.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention, layers
from repro.models.common import KeyGen, Params, init_stacked, resolve_dtype


class EncDecState(NamedTuple):
    pos: jax.Array  # [B] decoder positions filled
    self_kv: attention.KVCache  # stacked [n_dec, B, L, KV, hd]
    cross_kv: attention.KVCache  # stacked [n_dec, B, S_enc, KV, hd]


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #
def _init_enc_layer(key: jax.Array, cfg: ArchConfig) -> Params:
    kg = KeyGen(key)
    return {
        "norm1": layers.init_norm(cfg),
        "attn": attention.init_attention(kg(), cfg),
        "norm2": layers.init_norm(cfg),
        "mlp": layers.init_mlp(kg(), cfg),
    }


def _init_dec_layer(key: jax.Array, cfg: ArchConfig) -> Params:
    kg = KeyGen(key)
    return {
        "norm1": layers.init_norm(cfg),
        "self_attn": attention.init_attention(kg(), cfg),
        "norm_x": layers.init_norm(cfg),
        "cross_attn": attention.init_cross_attention(kg(), cfg),
        "norm2": layers.init_norm(cfg),
        "mlp": layers.init_mlp(kg(), cfg),
    }


def init(key: jax.Array, cfg: ArchConfig) -> Params:
    kg = KeyGen(key)
    pdtype = resolve_dtype(cfg.param_dtype)
    return {
        "embed": layers.init_embedding(kg(), cfg),
        "dec_pos": (
            jax.random.normal(kg(), (cfg.decoder_seq_len, cfg.d_model), jnp.float32) * 0.02
        ).astype(pdtype),
        "encoder": init_stacked(
            kg(), cfg.n_encoder_layers, lambda k: _init_enc_layer(k, cfg)
        ),
        "enc_norm": layers.init_norm(cfg),
        "decoder": init_stacked(kg(), cfg.n_layers, lambda k: _init_dec_layer(k, cfg)),
        "dec_norm": layers.init_norm(cfg),
    }


# --------------------------------------------------------------------------- #
# Encoder
# --------------------------------------------------------------------------- #
def encode(params: Params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: [B, S_enc, D] stub embeddings -> encoder output [B, S_enc, D]."""
    x = frames.astype(resolve_dtype(cfg.dtype))
    S = x.shape[1]
    x = x + layers.sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)

    def layer_fn(x, lp):
        h = layers.apply_norm(lp["norm1"], cfg, x)
        x = x + attention.forward(lp["attn"], cfg, h, causal=False)
        h = layers.apply_norm(lp["norm2"], cfg, x)
        return x + layers.apply_mlp(lp["mlp"], cfg, h), None

    x, _ = jax.lax.scan(layer_fn, x, params["encoder"], unroll=cfg.scan_unroll)
    return layers.apply_norm(params["enc_norm"], cfg, x)


def build_cross_kv(params: Params, cfg: ArchConfig, enc_out: jax.Array) -> attention.KVCache:
    """Precompute the decoder cross-attention KV — part of the reusable
    context state (stored once per audio context, reused across requests)."""

    def per_layer(lp):
        return attention.cross_kv(lp["cross_attn"], cfg, enc_out)

    return jax.vmap(per_layer, in_axes=(0,))(params["decoder"])


# --------------------------------------------------------------------------- #
# Decoder
# --------------------------------------------------------------------------- #
def _dec_embed(params: Params, cfg: ArchConfig, tokens: jax.Array, offset) -> jax.Array:
    x = layers.embed_tokens(params["embed"], cfg, tokens)
    S = tokens.shape[1]
    pos = offset[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    pos = jnp.minimum(pos, cfg.decoder_seq_len - 1)
    return x + jnp.take(params["dec_pos"], pos, axis=0).astype(x.dtype)


def forward(
    params: Params, cfg: ArchConfig, frames: jax.Array, dec_tokens: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Training forward: encode frames, causally decode tokens. Returns
    (logits [B, S_dec, V], aux=0)."""
    enc_out = encode(params, cfg, frames)
    B = dec_tokens.shape[0]
    x = _dec_embed(params, cfg, dec_tokens, jnp.zeros((B,), jnp.int32))

    def layer_fn(x, lp):
        h = layers.apply_norm(lp["norm1"], cfg, x)
        x = x + attention.forward(lp["self_attn"], cfg, h, causal=True)
        h = layers.apply_norm(lp["norm_x"], cfg, x)
        ckv = attention.cross_kv(lp["cross_attn"], cfg, enc_out)
        x = x + attention.cross_attend(lp["cross_attn"], cfg, h, ckv)
        h = layers.apply_norm(lp["norm2"], cfg, x)
        return x + layers.apply_mlp(lp["mlp"], cfg, h), None

    x, _ = jax.lax.scan(layer_fn, x, params["decoder"], unroll=cfg.scan_unroll)
    x = layers.apply_norm(params["dec_norm"], cfg, x)
    return layers.lm_logits(params["embed"], cfg, x), jnp.float32(0.0)


def init_state(
    cfg: ArchConfig, batch: int, max_len: int, enc_len: Optional[int] = None, dtype=None
) -> EncDecState:
    enc_len = enc_len or cfg.encoder_seq_len
    dtype = dtype or resolve_dtype(cfg.dtype)
    n = cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def z(shape):
        return jnp.zeros(shape, dtype)

    return EncDecState(
        pos=jnp.zeros((batch,), jnp.int32),
        self_kv=attention.KVCache(
            z((n, batch, max_len, kv, hd)), z((n, batch, max_len, kv, hd))
        ),
        cross_kv=attention.KVCache(
            z((n, batch, enc_len, kv, hd)), z((n, batch, enc_len, kv, hd))
        ),
    )


def prefill(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,
    state: EncDecState,
    embeds: Optional[jax.Array] = None,  # audio frames (stub embeddings)
) -> Tuple[jax.Array, EncDecState]:
    """Decoder prefill.  If ``embeds`` is given the audio context is encoded
    and its cross-KV written into the state; otherwise the state's cross-KV is
    *reused* stored context state (the paper's technique)."""
    cross = state.cross_kv
    if embeds is not None:
        enc_out = encode(params, cfg, embeds)
        cross = build_cross_kv(params, cfg, enc_out)
    B, S = tokens.shape
    offset = state.pos
    x = _dec_embed(params, cfg, tokens, offset)

    def layer_fn(x, per):
        lp, kv, ckv = per
        h = layers.apply_norm(lp["norm1"], cfg, x)
        out, kv = attention.prefill(lp["self_attn"], cfg, h, kv, offset)
        x = x + out
        h = layers.apply_norm(lp["norm_x"], cfg, x)
        x = x + attention.cross_attend(lp["cross_attn"], cfg, h, ckv)
        h = layers.apply_norm(lp["norm2"], cfg, x)
        return x + layers.apply_mlp(lp["mlp"], cfg, h), kv

    x, self_kv = jax.lax.scan(
        layer_fn, x, (params["decoder"], state.self_kv, cross), unroll=cfg.scan_unroll
    )
    x = layers.apply_norm(params["dec_norm"], cfg, x[:, -1:])
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, EncDecState(pos=offset + S, self_kv=self_kv, cross_kv=cross)


def decode(
    params: Params, cfg: ArchConfig, tokens: jax.Array, state: EncDecState
) -> Tuple[jax.Array, EncDecState]:
    pos = state.pos
    x = _dec_embed(params, cfg, tokens, pos)

    def layer_fn(x, per):
        lp, kv, ckv = per
        h = layers.apply_norm(lp["norm1"], cfg, x)
        # a one-layer stack: attention.decode takes the stacked layout
        out, kv = attention.decode(
            lp["self_attn"], cfg, h, attention.KVCache(kv.k[None], kv.v[None]), 0, pos
        )
        kv = attention.KVCache(kv.k[0], kv.v[0])
        x = x + out
        h = layers.apply_norm(lp["norm_x"], cfg, x)
        x = x + attention.cross_attend(lp["cross_attn"], cfg, h, ckv)
        h = layers.apply_norm(lp["norm2"], cfg, x)
        return x + layers.apply_mlp(lp["mlp"], cfg, h), kv

    x, self_kv = jax.lax.scan(
        layer_fn, x, (params["decoder"], state.self_kv, state.cross_kv),
        unroll=cfg.scan_unroll,
    )
    x = layers.apply_norm(params["dec_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, EncDecState(pos=pos + 1, self_kv=self_kv, cross_kv=state.cross_kv)
