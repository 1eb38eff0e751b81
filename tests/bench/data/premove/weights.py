"""The benchmark's ``bench/weights.py`` as it was before each architecture's
code moved into its model module (``bench/models/dense_gqa.py``), kept
unchanged so that the tests can show the move changed no number.

Seeded random weights of a dense GQA decoder, made on the device.

One jitted call makes every weight from the seed, in bf16 (the type they
are served in), laid out as the serving program's parameter tree for a
dense decoder: ``{"embed": {"table"[, "head"]}, "layers": [stacked layer],
"final_norm"}``.  The benchmark gives these weights to the program, and its
plain reference (``bench/reference.py``) reads the same ones: the reference
takes nothing the program made.

Sizes: the embedding table at std 0.02; every projection at std
``1 / sqrt(fan_in)``; q/k/v biases at std 0.02; norm scales ``1 + N(0, 0.02)``
so that a norm that skipped its scale would show.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


DTYPE = jnp.bfloat16


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed: its low and high 32 bits both
    count (``PRNGKey`` alone would keep only the low ones)."""
    s = int(seed) % (1 << 64)
    lo, hi = s & 0xFFFFFFFF, s >> 32
    key = jax.random.PRNGKey(jnp.uint32(lo))
    return jax.random.fold_in(key, jnp.uint32(hi))


def shapes(d: Dims) -> dict:
    """Leaf shapes of the parameter tree (``L`` leading on layer leaves)."""
    D, L, H, KV, hd, F, V = (d.d_model, d.n_layers, d.n_heads, d.n_kv_heads,
                             d.head_dim, d.d_ff, d.vocab)
    attn = {"wq": (L, D, H, hd), "wk": (L, D, KV, hd), "wv": (L, D, KV, hd),
            "wo": (L, H, hd, D)}
    if d.qkv_bias:
        attn.update({"bq": (L, H, hd), "bk": (L, KV, hd), "bv": (L, KV, hd)})
    embed = {"table": (V, D)}
    if not d.tied:
        embed["head"] = (D, V)
    layer = {
        "norm1": {"scale": (L, D)}, "attn": attn, "norm2": {"scale": (L, D)},
        "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)},
    }
    return {"embed": embed, "layers": [layer], "final_norm": {"scale": (D,)}}


def _std(path: str, shape) -> float:
    """Embedding and biases 0.02; a projection 1 / sqrt(fan_in)."""
    name = path.rsplit("/", 1)[-1]
    if name == "table" or name.startswith("b"):
        return 0.02
    if name == "head":
        return shape[0] ** -0.5
    if name == "wo":  # [L, H, hd, D]
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5  # [L, in, ...]


def _leaf(key, path: str, shape):
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        return (1.0 + 0.02 * x).astype(DTYPE)
    return (x * _std(path, shape)).astype(DTYPE)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, list):
        for i, t in enumerate(tree):
            yield from _paths(t, f"{prefix}/{i}")
    else:
        yield prefix, tree


def _build(tree, leaves, prefix=""):
    if isinstance(tree, dict):
        return {k: _build(v, leaves, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, list):
        return [_build(t, leaves, f"{prefix}/{i}") for i, t in enumerate(tree)]
    return leaves[prefix]


def make(d: Dims, seed: int, device=None):
    """Every weight from ``seed``, in one jitted call, on ``device``."""
    tree = shapes(d)
    paths = list(_paths(tree))

    @functools.partial(
        jax.jit,
        out_shardings=None if device is None else jax.sharding.SingleDeviceSharding(device),
    )
    def gen(key):
        leaves = {p: _leaf(jax.random.fold_in(key, i), p, s)
                  for i, (p, s) in enumerate(paths)}
        return _build(tree, leaves)

    return gen(key_from_seed(seed))
