"""Int8 KV (de)quantisation Pallas kernels — the storage-tier hot path.

Layout contract: quantisation is symmetric per-(row) over the trailing
channel dim (head_dim), matching ``ref.kv_quant_ref``.  The dequant kernel
runs on load (storage -> HBM) fused over row blocks so reused KV never
round-trips through fp32 HBM tensors.

VMEM: row-block x hd x (1B int8 + 2-4B float) — e.g. 256 rows x 128 ch
~= 0.16 MB per buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def supported(x) -> bool:
    return x.ndim >= 2 and x.shape[-1] >= 8


def _flatten(x):
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return x.reshape(rows, x.shape[-1]), x.shape


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def kv_quant(x: jax.Array, *, interpret: bool = False, block_rows: int = 256):
    xf, orig_shape = _flatten(x)
    rows, hd = xf.shape
    br = min(block_rows, max(rows, 1))
    pad = (-rows) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)), constant_values=1.0)
    n = (rows + pad) // br

    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((br, hd), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, hd), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows + pad, hd), jnp.int8),
            jax.ShapeDtypeStruct((rows + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xf)
    q = q[:rows].reshape(orig_shape)
    s = s[:rows].reshape(orig_shape[:-1] + (1,))
    return q, s


@functools.partial(jax.jit, static_argnames=("dtype", "interpret", "block_rows"))
def kv_dequant(
    q: jax.Array, scale: jax.Array, *, dtype=jnp.bfloat16, interpret: bool = False,
    block_rows: int = 256,
):
    qf, orig_shape = _flatten(q)
    sf = scale.reshape(qf.shape[0], 1)
    rows, hd = qf.shape
    br = min(block_rows, max(rows, 1))
    pad = (-rows) % br
    if pad:
        qf = jnp.pad(qf, ((0, pad), (0, 0)))
        sf = jnp.pad(sf, ((0, pad), (0, 0)))
    n = (rows + pad) // br

    out = pl.pallas_call(
        _dequant_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, hd), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, hd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, hd), dtype),
        interpret=interpret,
    )(qf, sf)
    return out[:rows].reshape(orig_shape)
