"""Production mesh construction (factory function — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model code places arrays with
    sharding constraints, not explicit-sharding types (``jax.make_mesh``
    defaults to ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16); the ``pod``
    axis is pure data-parallel (crosses DCI once per step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))
