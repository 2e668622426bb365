"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: since JAX 0.9 its axes default to
    Explicit, while this code shards through ``NamedSharding`` constraints
    and lets the compiler propagate the rest."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Whatever this host has (tests / CPU smoke): (data, model)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
