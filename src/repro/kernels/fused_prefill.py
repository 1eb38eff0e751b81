"""Selective-recompute fused prefill flash kernel (Pallas).

CacheBlend-style non-prefix reuse assembles one KV buffer per request out of
stored chunk spans (preloaded, possibly from several source entries) plus
the fresh K/V of the tokens chosen for recompute, then runs attention for
ONLY those recompute tokens against the full buffer.  The query side is a
*gappy* subset of positions — not a suffix — so this is
``flash_prefill._kernel`` with position-based masking generalised to
arbitrary (ascending) query positions:

    keep(p, s)  iff  kv_pos[s] >= 0  and  kv_pos[s] <= q_pos[p]   (and window)

plus a block-level early-out: a kv block whose smallest valid position lies
beyond the q block's largest position is fully masked, and a fully-masked
block is an exact no-op of the online-softmax recurrence (alpha == 1,
p == 0), so skipping its arithmetic changes nothing.  With a small recompute
fraction most (q, kv) tiles are in the strictly-causal region anyway — the
compute saving of selective recompute comes from the short q side.

Grid/BlockSpec layout is inherited unchanged from ``flash_prefill``
(``head_specs``): grid = (B, H, nQ, nKV), kv innermost, lane-merged q/k/v
views; running (m, l, acc) in VMEM scratch.
Exactness contract (tests/test_fusion.py): ``ref.fused_prefill_ref`` at full
query coverage equals plain causal attention bitwise; ``lm.prefill_fused`` at
r=1.0 equals a plain full ``lm.prefill`` bitwise, except on qwen2 (q/k/v
biases), where XLA sums the two in a different order and they agree to f32
reordering tolerance.
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
    q_ref, k_ref, v_ref, qp_ref, kp_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # scratch
    *, window: Optional[int], n_kv: int, scale: float,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[0]  # [bq, 1]
    kp = kp_ref[0]  # [1, bkv]

    # Early-out: every kv position in this block is invalid or beyond the
    # q block's causal reach -> the whole tile is masked, an exact no-op.
    kp_min = jnp.min(jnp.where(kp >= 0, kp, 2**30))
    q_max = jnp.max(qp)

    @pl.when(kp_min <= q_max)
    def _update():
        q = q_ref[0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0].astype(jnp.float32)  # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]

        mask = kp >= 0
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
    static_argnames=("window", "interpret", "block_q", "block_kv"),
)
def fused_flash_attention(
    q: jax.Array,  # [B, Sq, H, hd] — recompute tokens only
    k: jax.Array,  # [B, Skv, KV, hd] — assembled context buffer
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, Sq] absolute (gappy) query positions
    kv_pos: jax.Array,  # [B, Skv] row positions (-1 invalid)
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
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    n_q, n_kv = Sq_p // bq, Skv_p // bkv

    q_spec, kv_spec, q_col, kv_row = head_specs(bq, bkv, hd, G)
    kernel = functools.partial(
        _kernel, window=window, n_kv=n_kv, scale=1.0 / (hd**0.5)
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_col, kv_row],
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
    )
    return out.reshape(B, Sq_p, H, hd)[:, :Sq]
