"""Flash attention Pallas kernel for (suffix-)prefill.

The paper's hot path: with KV reuse, prefill runs the *new* tokens' queries
against [stored-prefix KV ++ new KV].  This kernel implements the
generalised position-masked attention of ``ref.attention_ref`` (causal
offsets via q_pos/kv_pos, sliding windows, invalid slots as kv_pos < 0) in
the canonical TPU flash pattern:

  grid = (B, H, nQ, nKV), kv innermost (sequential on TPU);
  running (m, l, acc) in VMEM scratch; output block revisited across the kv
  axis and finalised on the last kv step.

TPU tiling: q/out are viewed lane-merged as ``[B, S, H*hd]`` and k/v as
``[B, Skv, KV*hd]`` (free reshapes), so every block's two minor dims are
``(rows, hd)`` — (8, 128)-aligned when ``hd % 128 == 0``, which
``supported`` requires.  Query positions come in as a ``[B, Sq, 1]`` column
and kv positions as ``[B, 1, Skv]`` rows, so the mask is a broadcast compare.

The working set stays in VMEM:
  q/out (bq, hd) + k/v (bkv, hd) + scores (bq, bkv) f32
  = bq*hd*(2+4) + 2*bkv*hd*2 + 4*bq*bkv  bytes
  ~= 128*128*6 + 2*128*128*2 + 4*128*128 ~= 0.23 MB  (bq=bkv=128, hd=128)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def supported(q, k, v, window: Optional[int] = None) -> bool:
    """Shapes the compiled TPU kernel accepts: a lane-aligned head dim (the
    lane-merged views take ``hd``-wide blocks)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    return (
        H % KV == 0
        and hd % 128 == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _kernel(
    q_ref, k_ref, v_ref, qp_ref, kp_ref,  # inputs
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

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bkv]

    mask = kp >= 0
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


def head_specs(bq: int, bkv: int, hd: int, G: int):
    """BlockSpecs of the per-query-head prefill grid (B, H, nQ, nKV) over the
    lane-merged views: q/out ``(bq, hd)`` at ``(b, iq, h)``, k/v
    ``(bkv, hd)`` at ``(b, ik, h // G)``, q-side ``[B, Sq, 1]`` columns and
    kv-side ``[B, 1, Skv]`` rows.  Shared by the flash, packed and fused
    prefill kernels."""
    q = pl.BlockSpec((1, bq, hd), lambda b, h, iq, ik: (b, iq, h))
    kv = pl.BlockSpec((1, bkv, hd), lambda b, h, iq, ik: (b, ik, h // G))
    q_col = pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0))
    kv_row = pl.BlockSpec((1, 1, bkv), lambda b, h, iq, ik: (b, 0, ik))
    return q, kv, q_col, kv_row


def head_scratch(bq: int, hd: int):
    """Running max, denominator and accumulator of one query block."""
    return [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, hd), jnp.float32),
    ]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "interpret", "block_q", "block_kv"),
)
def flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, Sq]
    kv_pos: jax.Array,  # [B, Skv]
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
        # padded queries mask everything out; final rows are dropped below
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-(2**30))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    n_q, n_kv = Sq_p // bq, Skv_p // bkv

    q_spec, kv_spec, q_col, kv_row = head_specs(bq, bkv, hd, G)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, n_kv=n_kv, scale=1.0 / (hd**0.5)
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
