"""Production meshes.

Single pod: 256 v5e chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16) — model
parallelism stays within a pod (ICI); the "pod" axis carries pure data
parallelism over the inter-pod link (DCI).

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh with Auto axis types (tests / small runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1):
    """Mesh over whatever devices exist (CPU smoke runs, examples)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))
