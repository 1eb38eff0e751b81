"""Decode attention Pallas kernel (one query token per sequence).

Memory-bound by design: each step streams the sequence's KV cache once
(the roofline term the serving engine lives on).  Grid (B, nKV): one grid
step reads a ``bkv``-row block of every KV head of one sequence, and the G
grouped query heads of each KV head attend it together, so the cache is read
exactly once; flash-style running softmax across kv blocks in VMEM scratch,
one row of it per KV head.

Cache layout: the kernel reads the decode state's stacked cache
``[n_layers, B, L, KV, hd]`` where it lies.  The layer index rides in SMEM
as a scalar-prefetch operand and picks the layer in the K/V ``index_map``,
so no layer is sliced out as a copy.  A k/v block is ``(bkv, KV, hd)``: its
two minor dims are the array's own, so the TPU compiler takes the cache in
the tiling it is stored in (``(KV, hd)`` tiles per row) and no relayout runs
before the call; head ``h`` is the strided read ``k_ref[:, h, :]``.  The
per-slot kv positions (and the optional valid bitmap) are viewed as
``[B, 1, L]`` so their blocks are ``(1, bkv)`` rows; the query positions
ride in SMEM beside the layer index.

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
    padding (a pad would copy the cache on every step).  ``k``/``v`` are the
    stacked ``[n_layers, B, L, KV, hd]`` caches."""
    B, Sq, H, hd = q.shape
    L, KV = k.shape[2], k.shape[3]
    return (
        Sq == 1
        and H % KV == 0
        and hd % 128 == 0
        and L % min(block_kv, L) == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _kernel(
    layer_ref, qpos_ref,  # scalar-prefetch: [1], [B] int32
    q_ref, k_ref, v_ref, kp_ref, valid_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, window: Optional[int], n_kv: int, kv_heads: int, scale: float,
    use_valid: bool,
):
    del layer_ref  # used by the index maps only
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[pl.program_id(0)]  # scalar
    kp = kp_ref[0]  # [1, bkv]
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window
    if use_valid:
        mask &= valid_ref[0] != 0

    for h in range(kv_heads):
        qg = q_ref[0, h].astype(jnp.float32)  # [G, hd]
        k = k_ref[:, h, :].astype(jnp.float32)  # [bkv, hd]
        v = v_ref[:, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            qg, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, bkv]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h]  # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret", "block_kv")
)
def decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k: jax.Array,  # [n_layers, B, L, KV, hd] — the stacked cache
    v: jax.Array,
    *,
    layer: jax.Array,  # int32 scalar — the layer of k/v to attend
    q_pos: jax.Array,  # [B, 1]
    kv_pos: jax.Array,  # [B, L]
    window: Optional[int] = None,
    kv_valid: Optional[jax.Array] = None,  # [B, L] bool
    interpret: bool = False,
    block_kv: int = 128,
) -> jax.Array:
    B, _, H, hd = q.shape
    L, KV = k.shape[2], k.shape[3]
    G = H // KV

    bkv = min(block_kv, max(L, 8))
    pad = (-L) % bkv
    if pad:  # off the compiled path (``supported``): copies the cache
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    Lp = L + pad
    n_kv = Lp // bkv
    use_valid = kv_valid is not None
    valid = (
        kv_valid.astype(jnp.int32) if use_valid else jnp.ones((B, Lp), jnp.int32)
    )

    # [B, 1, H, hd] -> [B, KV, G, hd]: the query heads grouped by KV head.
    qg = q[:, 0].reshape(B, KV, G, hd)
    qp = q_pos.reshape(B).astype(jnp.int32)
    li = jnp.reshape(layer, (1,)).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, window=window, n_kv=n_kv, kv_heads=KV, scale=1.0 / (hd**0.5),
        use_valid=use_valid,
    )
    kv_spec = pl.BlockSpec(
        (None, None, bkv, KV, hd), lambda b, ik, li, p: (li[0], b, ik, 0, 0)
    )
    row_spec = pl.BlockSpec((1, 1, bkv), lambda b, ik, li, p: (b, 0, ik))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, ik, li, p: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), lambda b, ik, li, p: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(
        li, qp, qg, k, v,
        kv_pos.astype(jnp.int32).reshape(B, 1, Lp), valid.reshape(B, 1, Lp),
    )
    return out.reshape(B, 1, H, hd)
