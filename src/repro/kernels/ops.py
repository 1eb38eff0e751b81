"""Public jit-safe kernel entry points with backend dispatch.

Dispatch policy (``REPRO_KERNEL_MODE`` env var or :func:`set_kernel_mode`):
  * ``auto`` (default)      — Pallas kernels on TPU, jnp reference elsewhere.
  * ``ref``                 — always the pure-jnp oracle (CPU dry-run path).
  * ``pallas_interpret``    — Pallas kernels in interpret mode (CPU kernel
                              validation; used by the kernel test suite).
  * ``pallas``              — Pallas compiled (TPU).

When the mode resolves to Pallas, an entry point runs its kernel or raises
:class:`KernelUnsupported` naming the op and shapes — it never quietly
substitutes the jnp reference.  The only jnp paths under Pallas are explicit
rules: a prefill with fewer than :data:`MIN_KERNEL_Q` query tokens, and
attention that carries a ``kv_valid`` bitmap.  Every dispatch decision is
counted per op (:func:`dispatch_counts`) so a run can show which path its
attention took.

The chunked SSD implementation lives here (it is jnp-level and runs on every
backend); its exactness oracle is ``ref.ssd_scan_ref``.
"""
from __future__ import annotations

import collections
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref

_MODE = None

# (op, "kernel" | "jnp") -> dispatch decisions.  Entry points run while jit
# traces, so this counts traced calls: one per call site per compilation,
# not one per execution of the compiled program.
_COUNTS: collections.Counter = collections.Counter()

# Prefill launches shorter than one 128-row query block run the jnp
# reference: such a launch is a single partial tile.
MIN_KERNEL_Q = 128


class KernelUnsupported(ValueError):
    """A Pallas dispatch met shapes its kernel cannot tile."""


def set_kernel_mode(mode: Optional[str]) -> None:
    """Override dispatch mode globally (None restores env/auto)."""
    global _MODE
    _MODE = mode


def kernel_mode() -> str:
    if _MODE is not None:
        return _MODE
    return os.environ.get("REPRO_KERNEL_MODE", "auto")


def _use_pallas() -> Tuple[bool, bool]:
    """Returns (use_pallas, interpret)."""
    mode = kernel_mode()
    if mode == "ref":
        return False, False
    if mode == "pallas":
        return True, False
    if mode == "pallas_interpret":
        return True, True
    if mode != "auto":
        raise ValueError(f"unknown kernel mode {mode!r}")
    return jax.default_backend() == "tpu", False


def dispatch_counts() -> Dict[str, Dict[str, int]]:
    """``{op: {"kernel": n, "jnp": m}}`` traced dispatch decisions so far."""
    out: Dict[str, Dict[str, int]] = {}
    for (op, path), n in sorted(_COUNTS.items()):
        out.setdefault(op, {"kernel": 0, "jnp": 0})[path] = n
    return out


def reset_dispatch_counts() -> None:
    _COUNTS.clear()


def _jnp(op: str) -> None:
    _COUNTS[(op, "jnp")] += 1


def _kernel(op: str, ok: bool, *arrays: jax.Array) -> None:
    """Count a kernel dispatch, or raise if the kernel cannot take it."""
    if not ok:
        shapes = ", ".join(f"{a.dtype}{list(a.shape)}" for a in arrays)
        raise KernelUnsupported(
            f"{op}: the Pallas kernel does not support operands {shapes} "
            f"(kernel mode {kernel_mode()!r}); run with REPRO_KERNEL_MODE=ref "
            "to use the jnp reference"
        )
    _COUNTS[(op, "kernel")] += 1


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #
# Peak-memory guard: route big attention through the q-chunked (flash-style)
# jnp path so the dry-run never materialises an O(Sq*Skv) score tensor.
CHUNKED_THRESHOLD = 2048 * 8192


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Generalised GQA attention — see ``ref.attention_ref`` for semantics."""
    use_pallas, interpret = _use_pallas()
    if use_pallas and kv_valid is None and q.shape[1] >= MIN_KERNEL_Q:
        from repro.kernels import flash_prefill

        _kernel("flash_prefill", flash_prefill.supported(q, k, v, window=window), q, k, v)
        return flash_prefill.flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window,
            interpret=interpret,
        )
    _jnp("flash_prefill")
    if kv_shard_enabled() and kv_valid is None:
        out = _kv_sharded_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window
        )
        if out is not None:
            return out
    if q.shape[1] * k.shape[1] >= CHUNKED_THRESHOLD:
        return ref.attention_ref_chunked(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window,
            kv_valid=kv_valid,
        )
    return ref.attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window, kv_valid=kv_valid
    )


def packed_attention(
    q: jax.Array,  # [B, Sq, H, hd] — packed token runs from several requests
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, Sq] segment-local positions
    kv_pos: jax.Array,  # [B, Skv]
    q_seg: jax.Array,  # [B, Sq] segment (request) id per query token
    kv_seg: jax.Array,  # [B, Skv] segment id per kv row
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    """Segment-masked attention over a packed ragged batch — the shared
    suffix-prefill kernel of batched admission.  See
    ``ref.packed_attention_ref`` for semantics."""
    use_pallas, interpret = _use_pallas()
    if use_pallas and q.shape[1] >= MIN_KERNEL_Q:
        from repro.kernels import packed_prefill

        _kernel("packed_prefill", packed_prefill.supported(q, k, v, window=window), q, k, v)
        return packed_prefill.packed_flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
            causal=causal, window=window, interpret=interpret,
        )
    _jnp("packed_prefill")
    return ref.packed_attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, q_seg=q_seg, kv_seg=kv_seg,
        causal=causal, window=window,
    )


def fused_prefill(
    q: jax.Array,  # [B, Sq, H, hd] — selectively-recomputed tokens only
    k: jax.Array,  # [B, Skv, KV, hd] — assembled context buffer
    v: jax.Array,
    *,
    q_pos: jax.Array,  # [B, Sq] absolute (gappy) query positions
    kv_pos: jax.Array,  # [B, Skv] row positions (-1 invalid)
    window: Optional[int] = None,
) -> jax.Array:
    """Selective-recompute attention over an assembled KV buffer — the
    CacheBlend-style fused prefill of non-prefix chunk reuse.  See
    ``ref.fused_prefill_ref`` for semantics and the r=1.0 bit-exactness
    contract vs plain full prefill."""
    use_pallas, interpret = _use_pallas()
    if use_pallas and q.shape[1] >= MIN_KERNEL_Q:
        from repro.kernels import fused_prefill as fpk

        _kernel("fused_prefill", fpk.supported(q, k, v, window=window), q, k, v)
        return fpk.fused_flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window,
            interpret=interpret,
        )
    _jnp("fused_prefill")
    return ref.fused_prefill_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window
    )


def decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k: jax.Array,  # [n_layers, B, L, KV, hd] — the stacked cache
    v: jax.Array,
    *,
    layer: jax.Array,  # int32 scalar — the layer of k/v to attend
    q_pos: jax.Array,
    kv_pos: jax.Array,
    window: Optional[int] = None,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """One query per sequence against layer ``layer`` of a stacked cache.
    The kernel reads that layer where it lies; the jnp reference indexes it
    out (a copy, which the CPU path does not mind)."""
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import decode_attention as dk

        _kernel("decode_attention", dk.supported(q, k, v), q, k, v)
        return dk.decode_attention(
            q, k, v, layer=layer, q_pos=q_pos, kv_pos=kv_pos, window=window,
            kv_valid=kv_valid, interpret=interpret,
        )
    _jnp("decode_attention")
    k = jax.lax.dynamic_index_in_dim(k, layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
    if kv_shard_enabled() and kv_valid is None:
        out = _kv_sharded_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True, window=window
        )
        if out is not None:
            return out
    return ref.attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True, window=window, kv_valid=kv_valid
    )


def paged_decode(
    q: jax.Array,  # [B, 1, H, hd]
    k_pool: jax.Array,  # [N_rows, KV, hd] — shared block pool, flat rows
    v_pool: jax.Array,
    *,
    block_table: jax.Array,  # [B, nb] int32 pool-block id per sequence block
    q_pos: jax.Array,  # [B, 1]
    block: int = 128,
    window: Optional[int] = None,
) -> jax.Array:
    """Decode attention gathering each sequence's live KV blocks from the
    shared pool via its block table — see ``ref.paged_decode_ref`` for
    semantics and the bit-exactness contract vs dense decode."""
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import paged_decode as pdk

        _kernel("paged_decode", pdk.supported(q, k_pool, v_pool, block), q, k_pool, v_pool)
        return pdk.paged_decode_attention(
            q, k_pool, v_pool, block_table=block_table, q_pos=q_pos,
            block=block, window=window, interpret=interpret,
        )
    _jnp("paged_decode")
    return ref.paged_decode_ref(
        q, k_pool, v_pool, block_table=block_table, q_pos=q_pos, block=block,
        window=window,
    )


def chunked_prefill(
    q: jax.Array,  # [B, C, H, hd] — up to C new tokens per sequence
    k_pool: jax.Array,  # [N_rows, KV, hd] — shared block pool, flat rows
    v_pool: jax.Array,
    *,
    block_table: jax.Array,  # [B, nb] int32 pool-block id per sequence block
    q_pos: jax.Array,  # [B, C] (-2^30 padding)
    block: int = 128,
    window: Optional[int] = None,
) -> jax.Array:
    """Chunked-prefill attention over the shared block pool — the unified
    continuous-batching launch mixing prefill-chunk rows with decode rows.
    See ``ref.chunked_prefill_ref`` for semantics and the bit-exactness
    contract vs dense suffix prefill."""
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import chunked_prefill as cpk

        _kernel("chunked_prefill", cpk.supported(q, k_pool, v_pool, block), q, k_pool, v_pool)
        return cpk.chunked_prefill_attention(
            q, k_pool, v_pool, block_table=block_table, q_pos=q_pos,
            block=block, window=window, interpret=interpret,
        )
    _jnp("chunked_prefill")
    return ref.chunked_prefill_ref(
        q, k_pool, v_pool, block_table=block_table, q_pos=q_pos, block=block,
        window=window,
    )


# --------------------------------------------------------------------------- #
# KV-sequence-sharded flash attention (shard_map over the model axis)
# --------------------------------------------------------------------------- #
# Beyond-paper distribution strategy (EXPERIMENTS.md §Perf): shard the KV
# length over the model axis and combine per-shard online-softmax pieces
#   m* = pmax(m_i);  l* = psum(l_i e^{m_i-m*});  o* = psum(o_i e^{m_i-m*}) / l*
# Collectives shrink from score-tensor all-reduces (O(Sq*Skv)) to stats+output
# (O(Sq*H*hd)); attention FLOPs and the score working set divide by the axis
# size; the KV cache stays length-sharded (HBM-safe for 32k-128k contexts
# with few KV heads).  Enable with REPRO_ATTN_KV_SHARD=1 (dry-run/TPU meshes).
def kv_shard_enabled() -> bool:
    return os.environ.get("REPRO_ATTN_KV_SHARD") == "1"


def _mesh_axes_for_kv_shard(batch: int, skv: int):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return None
    m = mesh.shape["model"]
    if m <= 1 or skv % m != 0:
        return None
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    bspec = baxes if (baxes and batch % bsize == 0) else None
    return mesh, bspec


def _kv_sharded_attention(q, k, v, *, q_pos, kv_pos, causal, window):
    from jax.sharding import PartitionSpec as P

    got = _mesh_axes_for_kv_shard(q.shape[0], k.shape[1])
    if got is None:
        return None
    mesh, b = got

    def local(q, k, v, qp, kp):
        m_loc, l_loc, o_loc = _flash_pieces(
            q, k, v, qp, kp, causal=causal, window=window
        )
        m_glob = jax.lax.pmax(m_loc, "model")
        corr = jnp.exp(m_loc - m_glob)  # [B, Sq, H]
        l_glob = jax.lax.psum(l_loc * corr, "model")
        o_glob = jax.lax.psum(o_loc * corr[..., None], "model")
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.astype(q.dtype)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(b, None, None, None),
            P(b, "model", None, None),
            P(b, "model", None, None),
            P(b, None),
            P(b, "model"),
        ),
        out_specs=P(b, None, None, None),
        check_vma=False,
    )
    return fn(q, k, v, q_pos, kv_pos)


def _flash_pieces(q, k, v, qp, kp, *, causal, window, q_chunk: int = 1024):
    """Unnormalised local softmax pieces over this shard's KV slice.

    Returns (m [B,Sq,H], l [B,Sq,H], o [B,Sq,H,hd]) with
    o = sum_s e^{score - m} v_s, computed in q chunks for O(c*Skv) memory."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def chunk(args):
        qi, qpi = args  # [B, c, H, hd], [B, c]
        qg = qi.reshape(B, -1, KV, G, hd).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kf) / jnp.sqrt(jnp.float32(hd))
        qpos = qpi[:, None, None, :, None].astype(jnp.int32)
        spos = kp[:, None, None, None, :].astype(jnp.int32)
        mask = spos >= 0
        if causal:
            mask &= spos <= qpos
        if window is not None:
            mask &= spos > qpos - window
        s = jnp.where(mask, s, ref.NEG_INF)
        m = jnp.max(s, axis=-1)  # [B,KV,G,c]
        p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p, vf)
        c = qi.shape[1]
        return (
            m.transpose(0, 3, 1, 2).reshape(B, c, H),
            l.transpose(0, 3, 1, 2).reshape(B, c, H),
            o.transpose(0, 3, 1, 2, 4).reshape(B, c, H, hd),
        )

    cq = min(q_chunk, Sq)
    pad = (-Sq) % cq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, pad)), constant_values=-(2**30))
    nc = (Sq + pad) // cq
    if nc == 1:
        m, l, o = chunk((q, qp))
    else:
        qc = q.reshape(B, nc, cq, H, hd).transpose(1, 0, 2, 3, 4)
        qpc = qp.reshape(B, nc, cq).transpose(1, 0, 2)
        ms, ls, os_ = jax.lax.map(chunk, (qc, qpc))
        m = ms.transpose(1, 0, 2, 3).reshape(B, Sq + pad, H)
        l = ls.transpose(1, 0, 2, 3).reshape(B, Sq + pad, H)
        o = os_.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pad, H, hd)
    return m[:, :Sq], l[:, :Sq], o[:, :Sq]


# --------------------------------------------------------------------------- #
# Chunked SSD (Mamba2) — linear-time, matmul-dominant formulation
# --------------------------------------------------------------------------- #
def ssd_chunked(
    x: jax.Array,  # [B, L, H, P]
    dt: jax.Array,  # [B, L, H] (softplus'd, >= 0)
    A: jax.Array,  # [H] (negative)
    B_: jax.Array,  # [B, L, G, S]
    C: jax.Array,  # [B, L, G, S]
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,  # [B, H, P, S]
) -> Tuple[jax.Array, jax.Array]:
    """State-space-dual chunked scan: within-chunk quadratic (MXU-friendly
    matmuls) + cross-chunk state recurrence.  Exactly equal (fp32 math) to the
    sequential oracle ``ref.ssd_scan_ref``.

    Returns (y [B,L,H,P], final_state [B,H,P,S]).
    """
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import ssd_scan

        _kernel("ssd_scan", ssd_scan.supported(x, dt, A, B_, C, chunk=chunk), x, B_)
        return ssd_scan.ssd_chunked(
            x, dt, A, B_, C, chunk=chunk, initial_state=initial_state,
            interpret=interpret,
        )
    _jnp("ssd_scan")
    return ssd_chunked_jnp(x, dt, A, B_, C, chunk=chunk, initial_state=initial_state)


def ssd_chunked_jnp(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B_: jax.Array,
    C: jax.Array,
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    Bsz, L, H, P = x.shape
    G, S = B_.shape[2], B_.shape[3]
    rep = H // G

    pad = (-L) % chunk
    if pad:
        # dt = 0 on padding => decay exp(0)=1 and zero update: state-safe.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    Bf = jnp.repeat(B_.astype(jnp.float32), rep, axis=2).reshape(Bsz, nc, chunk, H, S)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2).reshape(Bsz, nc, chunk, H, S)
    Af = A.astype(jnp.float32)

    a = dtf * Af[None, None, None, :]  # [B,nc,Q,H], <= 0
    cum = jnp.cumsum(a, axis=2)  # inclusive cumsum within chunk

    # Within-chunk ("diagonal") term: y[t] += sum_{s<=t} (C_t.B_s) e^{cum_t-cum_s} dt_s x_s
    CB = jnp.einsum("bnqhs,bnkhs->bnhqk", Cf, Bf)  # [B,nc,H,Q,Q]
    # decay[t, s] = exp(cum_t - cum_s), masked to s <= t
    ct = cum.transpose(0, 1, 3, 2)  # [B,nc,H,Q]
    dmat = ct[:, :, :, :, None] - ct[:, :, :, None, :]  # cum_t - cum_s
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    dmat = jnp.where(tri[None, None, None], dmat, -jnp.inf)
    decay = jnp.exp(dmat)  # [B,nc,H,Q,Q]
    M = CB * decay * dtf.transpose(0, 1, 3, 2)[:, :, :, None, :]  # * dt_s
    y_diag = jnp.einsum("bnhqk,bnkhp->bnqhp", M, xf)

    # Per-chunk end-state contribution: sum_s e^{cum_{Q-1}-cum_s} dt_s x_s ⊗ B_s
    end_decay = jnp.exp(ct[:, :, :, -1:] - ct)  # [B,nc,H,Q]
    weighted_x = xf * (dtf * end_decay.transpose(0, 1, 3, 2))[..., None]  # [B,nc,Q,H,P]
    chunk_states = jnp.einsum("bnqhp,bnqhs->bnhps", weighted_x, Bf)

    # Cross-chunk recurrence over nc chunks.
    chunk_decay = jnp.exp(ct[:, :, :, -1])  # [B,nc,H] total decay of each chunk
    h0 = (
        jnp.zeros((Bsz, H, P, S), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def chunk_step(h, inp):
        st, dec = inp  # [B,H,P,S], [B,H]
        h_in = h  # state BEFORE this chunk
        h = h * dec[:, :, None, None] + st
        return h, h_in

    hT, h_inits = jax.lax.scan(
        chunk_step, h0, (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    h_inits = jnp.moveaxis(h_inits, 0, 1)  # [B,nc,H,P,S]

    # Off-diagonal term: y[t] += e^{cum_t} * (C_t · h_init)
    y_off = jnp.einsum("bnqhs,bnhps->bnqhp", Cf, h_inits)
    y_off = y_off * jnp.exp(cum)[..., None]

    y = (y_diag + y_off).reshape(Bsz, Lp, H, P)[:, :L]
    return y.astype(x.dtype), hT


def ssd_decode(
    state: jax.Array,
    x_t: jax.Array,
    dt_t: jax.Array,
    A: jax.Array,
    B_t: jax.Array,
    C_t: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """O(1) single-token SSD update (see ``ref.ssd_decode_ref``)."""
    return ref.ssd_decode_ref(state, x_t, dt_t, A, B_t, C_t)


# --------------------------------------------------------------------------- #
# KV int8 (de)quantisation for the storage/transfer tier
# --------------------------------------------------------------------------- #
def kv_quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import kv_quant as kq

        _kernel("kv_quant", kq.supported(x), x)
        return kq.kv_quant(x, interpret=interpret)
    _jnp("kv_quant")
    return ref.kv_quant_ref(x)


def kv_dequant(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    use_pallas, interpret = _use_pallas()
    if use_pallas:
        from repro.kernels import kv_quant as kq

        _kernel("kv_dequant", kq.supported(q), q)
        return kq.kv_dequant(q, scale, dtype=dtype, interpret=interpret)
    _jnp("kv_dequant")
    return ref.kv_dequant_ref(q, scale, dtype=dtype)
