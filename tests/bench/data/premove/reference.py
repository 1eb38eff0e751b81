"""The benchmark's ``bench/reference.py`` as it was before each architecture's
code moved into its model module (``bench/models/dense_gqa.py``), kept
unchanged so that the tests can show the move changed no number.

The plain reference: a dense GQA decoder's forward pass in float32.

Written from the published description of the configurations (Qwen2,
Mistral-NeMo): token embedding; per layer RMSNorm, q/k/v projections (with
biases where the configuration has them), rotary positions (rotate-half,
``theta`` as configured), causal grouped-query attention (query head ``h``
reads key/value head ``h // (n_heads / n_kv_heads)``) scaled by
``1/sqrt(head_dim)``, output projection and residual, RMSNorm, SwiGLU MLP and
residual; a final RMSNorm and the output head (the embedding table itself
where tied).  It imports nothing of the serving program and reads only the
weights of ``bench/weights.py``.

It runs layer by layer over one whole sequence, padded to a power of two
from ``CHUNK`` up to ``PAD`` tokens and to a multiple of ``PAD`` beyond, so
that the compiled layer is reused (padding sits after the real tokens and,
under the causal mask, changes none of them), with the
attention taken in blocks of ``CHUNK`` queries so that it fits beside the
weights.  Every matmul runs at ``Precision.HIGHEST``.

``fmt`` lowers the precision for the negative control: every matmul
operand (weights, activations, attention probabilities) rounded to a float
format of ``fmt = (exponent_bits, mantissa_bits)`` before the product, the
product accumulated in float32.  ``FP8 = (4, 3)`` is float8 e4m3, the
nearest step below the bf16 the configurations serve in.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


PAD = 4096
CHUNK = 512
FP8 = (4, 3)
HIGHEST = jax.lax.Precision.HIGHEST


def _rp(x, fmt):
    if fmt is None:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fmt[0], mantissa_bits=fmt[1])


def _mm(eq: str, a, b, fmt):
    return jnp.einsum(eq, _rp(a, fmt), _rp(b, fmt), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta: float):
    """x [S, heads, hd] at positions pos [S]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _layer(x, lp, *, d: Dims, fmt):
    """One decoder layer on x [S, D] float32; ``lp`` is the layer's weights."""
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    S = x.shape[0]
    KV, G, hd = d.n_kv_heads, d.n_heads // d.n_kv_heads, d.head_dim
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _rms(x, w["norm1"]["scale"], d.norm_eps)
    a = w["attn"]
    q = _mm("sd,dhe->she", h, a["wq"], fmt)
    k = _mm("sd,dke->ske", h, a["wk"], fmt)
    v = _mm("sd,dke->ske", h, a["wv"], fmt)
    if d.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q, pos, d.rope_theta).reshape(S, KV, G, hd)
    k = _rope(k, pos, d.rope_theta)

    def block(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * CHUNK, CHUNK, axis=0)
        s = _mm("qkge,ske->kgqs", qc, k, fmt) / np.sqrt(hd)
        qpos = c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
        keep = pos[None, :] <= qpos[:, None]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,ske->qkge", p, v, fmt).reshape(CHUNK, d.n_heads, hd)

    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, d.n_heads, hd)
    x = x + _mm("she,hed->sd", o, a["wo"], fmt)
    h = _rms(x, w["norm2"]["scale"], d.norm_eps)
    f = w["ffn"]
    g = _mm("sd,df->sf", h, f["w_gate"], fmt)
    u = _mm("sd,df->sf", h, f["w_up"], fmt)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, f["w_down"], fmt)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _embed(table, tokens, *, d: Dims, fmt):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _logits(x_rows, final_scale, head, *, d: Dims, fmt):
    h = _rms(x_rows, final_scale.astype(jnp.float32), d.norm_eps)
    w = head.astype(jnp.float32)
    if d.tied:
        return _mm("sd,vd->sv", h, w, fmt)
    return _mm("sd,dv->sv", h, w, fmt)


def logits(
    weights: dict, d: Dims, tokens: Sequence[int], rows: Sequence[int],
    fmt: Optional[Tuple[int, int]] = None,
) -> np.ndarray:
    """Float32 logits [len(rows), vocab] after each position in ``rows`` of
    the sequence ``tokens``."""
    n = len(tokens)
    s_pad = CHUNK
    while s_pad < min(n, PAD):
        s_pad *= 2
    if n > PAD:
        s_pad = -(-n // PAD) * PAD
    toks = np.zeros((s_pad,), np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"]["table"], jnp.asarray(toks), d=d, fmt=fmt)
    stack = weights["layers"][0]
    for i in range(d.n_layers):
        lp = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
        x = _layer(x, lp, d=d, fmt=fmt)
    head = weights["embed"]["table"] if d.tied else weights["embed"]["head"]
    x_rows = x[jnp.asarray(np.asarray(rows, np.int32))]
    out = _logits(x_rows, weights["final_norm"]["scale"], head, d=d, fmt=fmt)
    return np.asarray(jax.device_get(out))


def served_rows(n_prompt: int, n_served: int) -> List[int]:
    """Positions whose logits chose each served token: the prompt's last
    position, then each served token's own (all but the last served)."""
    return list(range(n_prompt - 1, n_prompt - 1 + n_served))
