"""Chunked-prefill Pallas kernel: multi-token rows over the shared KV block
pool — the unified continuous-batching launch.

This is ``paged_decode`` generalised from one query per sequence to a chunk
of up to ``C`` new tokens per sequence, all attending through the same
block-table indirection.  One launch therefore serves a MIXED batch: decode
rows (1 valid token at the live length), prefill-chunk rows (up to ``C``
block-aligned new tokens whose K/V the caller has already scattered into the
pool), and idle rows (all padding).  That mix is what lets the serving
engine interleave long suffix-prefills with in-flight decode instead of
stalling decode behind admission (Sarathi-style chunked prefill).

Grid (B, KV, nb) exactly as in ``paged_decode``: the block table rides in as
a scalar-prefetch operand so the k/v BlockSpec index maps DMA pool block
``table[b, j]`` directly, and the G grouped query heads of a KV head are
processed together, so each pool block is read once per head group.

TPU tiling: the pool is viewed lane-merged as ``[N_rows, KV*hd]`` and the
queries as ``[B, C, H*hd]`` (the pool's view is a relayout copy of the whole
pool on the TPU, as in ``paged_decode``).  A KV head's G query heads
are adjacent in the head axis, so one query block is ``(C, G*hd)`` at
``(b, 0, h)`` and the kernel walks the G heads as static ``hd``-wide lane
slices; the output is written back in the same layout, so no transpose
surrounds the launch.  Positions come in as a ``[B, C, 1]`` column.

Validity is purely positional per query: row ``r`` of table entry ``j``
holds sequence position ``j*block + r``, so ``pos <= q_pos[c]`` covers
causality within the chunk, the boundary block's tail, AND 0-padded table
entries (dump-block positions exceed every valid query); padding queries
(``q_pos`` = -2^30) mask every key and emit zeros.
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
    """Shapes the compiled TPU kernel accepts: chunks no longer than a pool
    block, a lane-aligned head dim and a sublane-aligned pool block."""
    B, C, H, hd = q.shape
    KV = k_pool.shape[1]
    return (
        1 <= C <= block
        and H % KV == 0
        and hd % 128 == 0
        and block % 8 == 0
        and k_pool.shape[0] % block == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _kernel(
    tbl_ref,  # scalar-prefetch: [B, nb] int32
    q_ref, k_ref, v_ref, qp_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # scratch
    *, nb: int, block: int, groups: int, hd: int, window: Optional[int],
    scale: float,
):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[...].astype(jnp.float32)  # [block, hd]
    v = v_ref[...].astype(jnp.float32)
    qp = qp_ref[0]  # [C, 1]

    # sequence position of each row of this table entry (by construction)
    kp = ib * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    mask = kp <= qp  # [C, block]
    if window is not None:
        mask &= kp > qp - window

    for g in range(groups):  # the KV head's query heads, hd-wide lane slices
        lanes = slice(g * hd, (g + 1) * hd)
        qg = q_ref[0, :, lanes].astype(jnp.float32)  # [C, hd]
        s = jax.lax.dot_general(
            qg, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [C, block]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[g]  # [C, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:, lanes] = acc_ref[:, lanes] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[g] = m_new

    @pl.when(ib == nb - 1)
    def _finalize():
        for g in range(groups):
            lanes = slice(g * hd, (g + 1) * hd)
            l = jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, :, lanes] = (acc_ref[:, lanes] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "window", "interpret")
)
def chunked_prefill_attention(
    q: jax.Array,  # [B, C, H, hd]
    k_pool: jax.Array,  # [N_rows, KV, hd] (N_rows = n_blocks * block)
    v_pool: jax.Array,
    *,
    block_table: jax.Array,  # [B, nb] int32
    q_pos: jax.Array,  # [B, C] (-2^30 padding)
    block: int = 128,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    B, C, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    nb = block_table.shape[1]

    kf = k_pool.reshape(-1, KV * hd)  # lane-merged views: a relayout copy on TPU
    vf = v_pool.reshape(-1, KV * hd)
    qf = q.reshape(B, C, H * hd)
    tbl = block_table.astype(jnp.int32)
    qp = q_pos.astype(jnp.int32).reshape(B, C, 1)

    kernel = functools.partial(
        _kernel, nb=nb, block=block, groups=G, hd=hd, window=window,
        scale=1.0 / (hd**0.5),
    )
    q_spec = pl.BlockSpec((1, C, G * hd), lambda b, h, ib, t: (b, 0, h))
    kv_spec = pl.BlockSpec((block, hd), lambda b, h, ib, t: (t[b, ib], h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nb),
        in_specs=[
            q_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, C, 1), lambda b, h, ib, t: (b, 0, 0)),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, C, 1), jnp.float32),
            pltpu.VMEM((G, C, 1), jnp.float32),
            pltpu.VMEM((C, G * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H * hd), q.dtype),
        interpret=interpret,
    )(tbl, qf, kf, vf, qp)
    return out.reshape(B, C, H, hd)
