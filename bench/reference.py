"""What every plain reference shares: precision, padding, blocking.

Each architecture's reference forward pass in float32 is its model
module's ``logits(weights, sizes, tokens, rows, fmt)``
(``bench/models/<m>.py``), written from the model's published description.
It imports nothing of the serving program and reads only the weights
``bench/weights.py`` made from the seed.

A reference runs layer by layer over one whole sequence, padded
(``padded``) to a power of two from ``CHUNK`` up to ``PAD`` tokens and to a
multiple of ``PAD`` beyond, so that the compiled layer is reused (padding
sits after the real tokens and, under the causal mask, changes none of
them), with attention taken in blocks of ``CHUNK`` queries so that it fits
beside the weights.  Every matmul runs at ``Precision.HIGHEST`` (``mm``).

``fmt`` lowers the precision for the negative control: every matmul
operand (weights, activations, attention probabilities) rounded to a float
format of ``fmt = (exponent_bits, mantissa_bits)`` before the product, the
product accumulated in float32.  ``FP8 = (4, 3)`` is float8 e4m3, the
nearest step below the bf16 the configurations serve in.
"""
from __future__ import annotations

from typing import List, Sequence

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


def mm(eq: str, a, b, fmt):
    """``einsum`` at the highest precision, both operands rounded to ``fmt``."""
    return jnp.einsum(eq, _rp(a, fmt), _rp(b, fmt), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, pos, theta: float):
    """x [S, heads, hd] at positions pos [S]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@jax.jit
def embed(table, tokens):
    """The rows of ``table`` for ``tokens``, in float32."""
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


def padded(tokens: Sequence[int]) -> np.ndarray:
    """``tokens`` as int32, zero-padded to the length the layers run at."""
    n = len(tokens)
    s_pad = CHUNK
    while s_pad < min(n, PAD):
        s_pad *= 2
    if n > PAD:
        s_pad = -(-n // PAD) * PAD
    toks = np.zeros((s_pad,), np.int32)
    toks[:n] = tokens
    return toks


def served_rows(n_prompt: int, n_served: int) -> List[int]:
    """Positions whose logits chose each served token: the prompt's last
    position, then each served token's own (all but the last served)."""
    return list(range(n_prompt - 1, n_prompt - 1 + n_served))
