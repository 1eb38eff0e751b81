"""Seeded random weights of a model, made on the device.

One jitted call makes every weight from the seed, in bf16 (the type they
are served in), laid out as the serving program's parameter tree: the leaf
shapes and init scales are the model module's (``bench/models/<m>.py``
``shapes`` and ``std``).  The benchmark gives these weights to the
program, and the model's plain reference reads the same ones: the
reference takes nothing the program made.

A leaf whose path ends in ``scale`` (a norm's) is ``1 + N(0, 0.02)``, so
that a norm that skipped its scale would show; every other leaf is
``N(0, std(path, shape))``.
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


def _leaf(key, path: str, shape, std):
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        return (1.0 + 0.02 * x).astype(DTYPE)
    return (x * std(path, shape)).astype(DTYPE)


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


def make(model, d, seed: int, device=None):
    """Every weight of ``model`` (a model module) at sizes ``d`` from
    ``seed``, in one jitted call, on ``device``."""
    tree = model.shapes(d)
    paths = list(_paths(tree))

    @functools.partial(
        jax.jit,
        out_shardings=None if device is None else jax.sharding.SingleDeviceSharding(device),
    )
    def gen(key):
        leaves = {p: _leaf(jax.random.fold_in(key, i), p, s, model.std)
                  for i, (p, s) in enumerate(paths)}
        return _build(tree, leaves)

    return gen(key_from_seed(seed))
