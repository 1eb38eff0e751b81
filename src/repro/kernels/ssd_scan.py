"""Chunked SSD (Mamba2) scan Pallas kernel.

State-space duality turns the selective-scan recurrence into, per chunk of Q
tokens: two MXU matmuls (C·Bᵀ masked-decay score and score·X) plus an O(1)
cross-chunk state update — the TPU-native adaptation of the CUDA selective
scan (DESIGN.md §3).

Grid (B, H, n_chunks), chunk axis innermost/sequential; the [P, S] running
state lives in VMEM scratch across chunk steps.  VMEM per step:
  x (Q,P) + B/C (Q,S) + score (Q,Q) + state (P,S) f32
  ~= 256*64*4 + 2*256*128*4 + 256*256*4 + 64*128*4 ~= 0.6 MB.
Alignment: Q=256, S=128, P=64 are MXU/lane friendly.

The kernel is exact vs the sequential oracle ``ref.ssd_scan_ref`` (fp32).
Gotcha honoured: padding tokens carry dt=0 => decay=1, zero update.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def supported(x, dt, A, B_, C, *, chunk: int = 256) -> bool:
    Bsz, L, H, P = x.shape
    G = B_.shape[2]
    return H % G == 0 and P <= 256 and B_.shape[3] <= 256


def _kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
    y_ref, hT_ref,
    state_ref,
    *, n_chunks: int, Q: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # [Q]
    a = a_ref[0, 0]  # scalar A_h
    bmat = b_ref[0, :, 0, :].astype(jnp.float32)  # [Q, S]
    cmat = c_ref[0, :, 0, :].astype(jnp.float32)  # [Q, S]

    adt = dt * a  # [Q], <= 0
    cum = jnp.cumsum(adt)  # inclusive
    # decay[t, s] = exp(cum_t - cum_s) for s <= t else 0
    dmat = cum[:, None] - cum[None, :]
    tri = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, dmat, 0.0)), 0.0)

    # within-chunk: y_diag = ((C Bᵀ) ⊙ decay ⊙ dt_s) X
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, Q]
    m = cb * decay * dt[None, :]
    y = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, P]

    # off-diagonal: y += e^{cum_t} * C_t · h_in
    h_in = state_ref[...]  # [P, S]
    y_off = jax.lax.dot_general(
        cmat, h_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, P]
    y = y + y_off * jnp.exp(cum)[:, None]
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: h_out = e^{cum_Q} h_in + Σ_s e^{cum_Q - cum_s} dt_s x_s ⊗ B_s
    end_decay = jnp.exp(cum[-1] - cum) * dt  # [Q]
    upd = jax.lax.dot_general(
        x * end_decay[:, None], bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [P, S]
    state_ref[...] = h_in * jnp.exp(cum[-1]) + upd

    @pl.when(ic == n_chunks - 1)
    def _fin():
        hT_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(
    x: jax.Array,  # [B, L, H, P]
    dt: jax.Array,  # [B, L, H]
    A: jax.Array,  # [H]
    B_: jax.Array,  # [B, L, G, S]
    C: jax.Array,  # [B, L, G, S]
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,  # [B, H, P, S]
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    Bsz, L, H, P = x.shape
    G, S = B_.shape[2], B_.shape[3]
    rep = H // G

    Q = min(chunk, max(L, 8))
    pad = (-L) % Q
    if pad:  # dt=0 on padding: no decay, no update (see module docstring)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // Q

    h0 = (
        jnp.zeros((Bsz, H, P, S), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    Af = A.astype(jnp.float32).reshape(H, 1)

    kernel = functools.partial(_kernel, n_chunks=nc, Q=Q)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, h, ic: (b, ic, h)),
            pl.BlockSpec((1, 1), lambda b, h, ic: (h, 0)),
            pl.BlockSpec((1, Q, 1, S), lambda b, h, ic, rep=rep: (b, ic, h // rep, 0)),
            pl.BlockSpec((1, Q, 1, S), lambda b, h, ic, rep=rep: (b, ic, h // rep, 0)),
            pl.BlockSpec((1, 1, P, S), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, 1, P, S), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, Lp, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, S), jnp.float32)],
        interpret=interpret,
    )(x, dt, Af, B_, C, h0)
    return y[:, :L], hT
