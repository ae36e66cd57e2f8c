"""Production mesh builders.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods = 512
chips as (pod=2, data=16, model=16); the ``pod`` axis is pure data
parallelism over DCN, ``model`` is the TP/EP (FiCCO) axis along one ICI
torus dimension, ``data`` covers FSDP + batch.

Functions (not module constants) so importing never touches device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: shardings stay out of the
    types, so the model's layer-scan carry keeps one type whatever the
    ``constrain`` calls inside it ask the partitioner for."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over however many (forced) host devices exist — used by
    examples and tests, never by the dry-run."""
    n = len(jax.devices())
    if model is None:
        model = n
    return make_mesh((n // model, model), ("data", "model"))
