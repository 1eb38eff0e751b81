"""Packed ragged suffix-prefill flash kernel (Pallas).

The batched admission path concatenates token runs from several requests into
ONE sequence: each request contributes a kv span ``[stored-prefix KV ++ new
KV]`` and a q span of its new (non-reused) tokens.  This kernel is
``flash_prefill._kernel`` plus one mask term — a segment id per q token and
per kv row, with cross-segment attention masked out — so many requests share
a single kernel launch instead of one launch each.

Positions stay segment-local (what each request would see alone), which keeps
the causal/sliding-window masking and the RoPE applied upstream identical to
the per-request path.  Exactness contract: with every segment's kv span
aligned to ``block_kv``, a fully-masked kv block is an exact no-op of the
online-softmax recurrence (alpha == 1, p == 0), so the packed output is
bit-identical to running each request alone — asserted by
``tests/test_packed.py``.

Grid/BlockSpec layout is inherited unchanged from ``flash_prefill``
(``head_specs``): grid = (B, H, nQ, nKV), kv innermost, lane-merged q/k/v
views; running (m, l, acc) in VMEM scratch.  The segment ids ride beside the
positions (a ``[B, Sq, 1]`` column and a ``[B, 1, Skv]`` row).  VMEM adds
only the two int32 id blocks on top of flash_prefill's ~0.23 MB working
set.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# ``supported``: the same tiling as flash_prefill, so the same shapes
from repro.kernels.flash_prefill import head_scratch, head_specs, supported  # noqa: F401

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, qp_ref, kp_ref, qs_ref, ks_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # scratch
    *, causal: bool, window: Optional[int], n_kv: int, scale: float,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [bq, hd]
    k = k_ref[0].astype(jnp.float32)  # [bkv, hd]
    v = v_ref[0].astype(jnp.float32)
    qp = qp_ref[0]  # [bq, 1]
    kp = kp_ref[0]  # [1, bkv]
    qs = qs_ref[0]  # [bq, 1]
    ks = ks_ref[0]  # [1, bkv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bkv]

    mask = kp >= 0
    mask &= qs == ks  # segment isolation
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "interpret", "block_q", "block_kv"),
)
def packed_flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, Sq] segment-local positions
    kv_pos: jax.Array,  # [B, Skv]
    q_seg: jax.Array,  # [B, Sq] segment id per query token
    kv_seg: jax.Array,  # [B, Skv] segment id per kv row
    causal: bool = True,
    window: Optional[int] = None,
    interpret: bool = False,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV

    bq = min(block_q, max(Sq, 8))
    bkv = min(block_kv, max(Skv, 8))
    pad_q = (-Sq) % bq
    pad_kv = (-Skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-(2**30))
        q_seg = jnp.pad(q_seg, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad_kv)), constant_values=-2)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    n_q, n_kv = Sq_p // bq, Skv_p // bkv

    q_spec, kv_spec, q_col, kv_row = head_specs(bq, bkv, hd, G)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, n_kv=n_kv, scale=1.0 / (hd**0.5)
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_col, kv_row, q_col, kv_row],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq_p, H * hd), q.dtype),
        scratch_shapes=head_scratch(bq, hd),
        interpret=interpret,
    )(
        q.reshape(B, Sq_p, H * hd),
        k.reshape(B, Skv_p, KV * hd),
        v.reshape(B, Skv_p, KV * hd),
        q_pos.astype(jnp.int32).reshape(B, Sq_p, 1),
        kv_pos.astype(jnp.int32).reshape(B, 1, Skv_p),
        q_seg.astype(jnp.int32).reshape(B, Sq_p, 1),
        kv_seg.astype(jnp.int32).reshape(B, 1, Skv_p),
    )
    return out.reshape(B, Sq_p, H, hd)[:, :Sq]
