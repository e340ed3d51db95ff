"""Mesh construction — the repo's one mesh constructor.

FUNCTIONS, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import; tests
import this module under a single real device).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple, axes: tuple, *, devices=None) -> Mesh:
    """A mesh whose axes are all ``AxisType.Auto``.

    Every mesh in the repo is built here.  ``jax.make_mesh`` otherwise
    gives *Explicit* axes, under which the repo's sharded scatters and
    sharding constraints do not type-check.  ``devices`` (any sequence of
    ``prod(shape)`` devices) keeps that exact device order; without it the
    devices come from ``jax.make_mesh``'s topology-aware order.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes),
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; ("pod", "data", "model") across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
