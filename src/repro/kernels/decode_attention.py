"""Decode attention Pallas kernel (one query token per sequence).

Memory-bound by design: each step streams the sequence's KV cache once
(the roofline term the serving engine lives on).  Grid (B, KV, nKV) with the
G grouped query heads of each KV head processed together so the cache is
read exactly once; flash-style running softmax across kv blocks in VMEM
scratch.

TPU tiling: the cache is viewed lane-merged as ``[B, L, KV*hd]`` (a free
reshape), so a k/v block is ``(bkv, hd)`` at ``(b, ik, h)``; the per-slot
kv positions (and the optional valid bitmap) are viewed as ``[B, 1, L]`` so
their blocks are ``(1, bkv)`` rows; the query positions ride in SMEM as a
scalar-prefetch operand.

Ring-buffer (SWA) caches work unchanged: slot validity and window masking
are position-based (kv_pos carries the absolute position per slot, -1 for
never-written).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def supported(q, k, v, block_kv: int = 128) -> bool:
    """Shapes the compiled TPU kernel accepts: one query per sequence, a
    lane-aligned head dim, and a cache length the kv block tiles without
    padding (a pad would copy the cache on every step)."""
    B, Sq, H, hd = q.shape
    L, KV = k.shape[1], k.shape[2]
    return (
        Sq == 1
        and H % KV == 0
        and hd % 128 == 0
        and L % min(block_kv, L) == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _kernel(
    qpos_ref,  # scalar-prefetch: [B] int32
    q_ref, k_ref, v_ref, kp_ref, valid_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, window: Optional[int], n_kv: int, scale: float, use_valid: bool,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qg = q_ref[0, 0].astype(jnp.float32)  # [G, hd]
    k = k_ref[0].astype(jnp.float32)  # [bkv, hd]
    v = v_ref[0].astype(jnp.float32)
    qp = qpos_ref[pl.program_id(0)]  # scalar
    kp = kp_ref[0]  # [1, bkv]

    s = jax.lax.dot_general(
        qg, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, bkv]

    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window
    if use_valid:
        mask &= valid_ref[0] != 0
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret", "block_kv")
)
def decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k: jax.Array,  # [B, L, KV, hd]
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, 1]
    kv_pos: jax.Array,  # [B, L]
    window: Optional[int] = None,
    kv_valid: Optional[jax.Array] = None,  # [B, L] bool
    interpret: bool = False,
    block_kv: int = 128,
) -> jax.Array:
    B, _, H, hd = q.shape
    L, KV = k.shape[1], k.shape[2]
    G = H // KV

    bkv = min(block_kv, max(L, 8))
    pad = (-L) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    Lp = L + pad
    n_kv = Lp // bkv
    use_valid = kv_valid is not None
    valid = (
        kv_valid.astype(jnp.int32) if use_valid else jnp.ones((B, Lp), jnp.int32)
    )

    # [B, 1, H, hd] -> [B, KV, G, hd] so one grid step covers a KV group.
    qg = q[:, 0].reshape(B, KV, G, hd)
    kf = k.reshape(B, Lp, KV * hd)  # lane-merged views, no copy
    vf = v.reshape(B, Lp, KV * hd)
    qp = q_pos.reshape(B).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, window=window, n_kv=n_kv, scale=1.0 / (hd**0.5), use_valid=use_valid
    )
    kv_spec = pl.BlockSpec((1, bkv, hd), lambda b, h, ik, p: (b, ik, h))
    row_spec = pl.BlockSpec((1, 1, bkv), lambda b, h, ik, p: (b, 0, ik))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik, p: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ik, p: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(
        qp, qg, kf, vf,
        kv_pos.astype(jnp.int32).reshape(B, 1, Lp), valid.reshape(B, 1, Lp),
    )
    return out.reshape(B, 1, H, hd)
