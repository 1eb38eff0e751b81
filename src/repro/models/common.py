"""Shared model utilities: parameter init, dtype policy, sharding helpers.

The model zoo is pure-JAX and dependency-free: parameters are nested-dict
pytrees produced by explicit ``init`` functions; forward passes are pure
functions of ``(params, config, inputs)``.  Sharding is expressed as a
parallel pytree of :class:`jax.sharding.PartitionSpec` built by
``repro.distributed.sharding`` — keeping the lowering path transparent for
the roofline analysis.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
}


def resolve_dtype(name) -> jnp.dtype:
    if isinstance(name, str):
        return _DTYPES[name]
    return name


# --------------------------------------------------------------------------- #
# Parameter initialisation
# --------------------------------------------------------------------------- #
def dense_init(key: jax.Array, shape: Sequence[int], dtype, fan_in: Optional[int] = None):
    """Lecun-normal init (stddev = 1/sqrt(fan_in)); fan_in defaults to the
    first dimension (our dense weights are stored ``[in, out...]``)."""
    fan_in = int(fan_in if fan_in is not None else shape[0])
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, tuple(shape), jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, shape: Sequence[int], dtype):
    return (jax.random.normal(key, tuple(shape), jnp.float32) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(tuple(shape), dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(tuple(shape), dtype)


class KeyGen:
    """Splits a PRNG key on demand; keeps init code linear and readable."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


# --------------------------------------------------------------------------- #
# Stacking (for scan-over-layers)
# --------------------------------------------------------------------------- #
def stack_layers(layer_params: Sequence[Params]) -> Params:
    """Stack a list of identical-structure param trees along a new axis 0."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *layer_params)


def init_stacked(key: jax.Array, n: int, init_one) -> Params:
    """Initialise ``n`` layers worth of parameters, stacked on axis 0.

    Uses vmap over per-layer keys so init stays fast and the result is a
    single stacked pytree suitable for ``lax.scan``.
    """
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


# --------------------------------------------------------------------------- #
# Sharding helper
# --------------------------------------------------------------------------- #
def maybe_shard(x: jax.Array, spec) -> jax.Array:
    """``with_sharding_constraint`` that no-ops when no mesh is active (so the
    same model code runs in single-device tests and in the dry-run)."""
    if spec is None or jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------------- #
# Numerics
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.silu(x_gate) * x_up


def count_tree_params(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def tree_bytes(params: Params) -> int:
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
    )


def cast_tree(params: Params, dtype) -> Params:
    dtype = resolve_dtype(dtype)
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params
    )
