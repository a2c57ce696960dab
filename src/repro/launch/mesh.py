"""Production meshes.

Single pod: 16x16 = 256 chips (data, model).
Multi-pod:  2x16x16 = 512 chips (pod, data, model); the pod axis extends
data parallelism across the inter-pod links (DCN/ICI), proving every
collective in the program shards over a third axis.

`make_production_mesh` is a function (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axis_names) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis in Auto sharding mode."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return make_auto_mesh((data, model), ("data", "model"))
