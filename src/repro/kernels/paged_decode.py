"""Paged decode-attention Pallas kernel: block-table gather over the shared
KV block pool.

Decode under the paged layout reads, per sequence, exactly the live
``block``-token blocks its block table names — nothing else leaves HBM.  The
pool is ONE array shared by every batch slot ([N_rows, KV, hd], rows
``j*block .. (j+1)*block`` forming pool block ``j``);
``block_table[b, j]`` is the pool block holding sequence ``b``'s tokens
``[j*block, (j+1)*block)``.  The table rides in as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) so the k/v BlockSpec index maps can
dereference it — the DMA for grid step (b, h, j) fetches pool block
``table[b, j]`` directly; no gathered copy of the cache is ever
materialised.

TPU tiling: the pool is viewed lane-merged as ``[N_rows, KV*hd]``, so one
grid step's k/v block is
``(block, hd)`` at block index ``(table[b, j], h)`` — both minor dims are
(8, 128)-aligned when ``hd % 128 == 0``.  The view is not free on the TPU:
the pool is stored in ``(KV, hd)`` tiles per row, so the compiler copies the
whole pool into the merged layout before each call (the dense decode kernel
reads ``(bkv, KV, hd)`` blocks instead; see ``decode_attention``).  The query
positions ride in SMEM as a second scalar-prefetch operand.

Grid (B, KV, nb) with the G grouped query heads of a KV head processed
together (the cache block is read once per head group), flash-style running
softmax across the table axis in VMEM scratch — structurally
``decode_attention`` with the kv axis indirected through the table.
Validity is positional: row ``r`` of table entry ``j`` holds sequence
position ``j*block + r``, so masking ``pos > q_pos`` covers the boundary
block's tail AND the 0-padded table entries (they point at the reserved dump
block, whose positions all exceed the query's) — no separate valid-bitmap
input is needed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def supported(q, k_pool, v_pool, block: int) -> bool:
    """Shapes the compiled TPU kernel accepts: one query per sequence, a
    lane-aligned head dim (the ``[N_rows, KV*hd]`` view takes ``hd``-wide
    blocks) and a sublane-aligned pool block."""
    B, Sq, H, hd = q.shape
    KV = k_pool.shape[1]
    return (
        Sq == 1
        and H % KV == 0
        and hd % 128 == 0
        and block % 8 == 0
        and k_pool.shape[0] % block == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _kernel(
    tbl_ref, qpos_ref,  # scalar-prefetch: [B, nb], [B] int32
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # scratch
    *, nb: int, block: int, window: Optional[int], scale: float,
):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qg = q_ref[0, 0].astype(jnp.float32)  # [G, hd]
    k = k_ref[...].astype(jnp.float32)  # [block, hd]
    v = v_ref[...].astype(jnp.float32)
    qp = qpos_ref[pl.program_id(0)]  # scalar

    s = jax.lax.dot_general(
        qg, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, block]

    # sequence position of each row of this table entry (by construction)
    kp = ib * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    mask = kp <= qp
    if window is not None:
        mask &= kp > qp - window
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

    @pl.when(ib == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "window", "interpret")
)
def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k_pool: jax.Array,  # [N_rows, KV, hd] (N_rows = n_blocks * block)
    v_pool: jax.Array,
    *,
    block_table: jax.Array,  # [B, nb] int32
    q_pos: jax.Array,  # [B, 1]
    block: int = 128,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    nb = block_table.shape[1]

    kf = k_pool.reshape(-1, KV * hd)  # lane-merged view: a relayout copy on TPU
    vf = v_pool.reshape(-1, KV * hd)
    # [B, 1, H, hd] -> [B, KV, G, hd]: one grid step covers a KV head group.
    qg = q[:, 0].reshape(B, KV, G, hd)
    tbl = block_table.astype(jnp.int32)
    qp = q_pos.reshape(B).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, nb=nb, block=block, window=window, scale=1.0 / (hd**0.5)
    )
    kv_spec = pl.BlockSpec((block, hd), lambda b, h, ib, t, p: (t[b, ib], h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ib, t, p: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ib, t, p: (b, h, 0, 0)),
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
    )(tbl, qp, qg, kf, vf)
    return out.reshape(B, 1, H, hd)
