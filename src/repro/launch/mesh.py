"""Device meshes.

Every mesh this repository builds goes through :func:`make_mesh`: JAX
0.9's ``jax.make_mesh`` defaults to Explicit axes, under which the
``with_sharding_constraint`` calls in ``sharding/`` and ``train/step.py``
and gathers inside a ``jit`` fail unless the caller enters
``jax.set_mesh``.  The serving and training code is written against
Auto axes (the compiler propagates shardings from the constraints), so
the meshes are built with ``AxisType.Auto``.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run launcher must set XLA_FLAGS before anything initializes devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """``jax.make_mesh`` (topology-aware device order) with Auto axes.
    ``devices`` defaults to all of ``jax.devices()``; pass a prefix to
    build a mesh over part of the host."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model) — DP across pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI tests (requires >= prod(shape) devices)."""
    return make_mesh(shape, axes)
