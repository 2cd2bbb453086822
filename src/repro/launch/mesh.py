"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
overrides the host device count and everything else must see 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (data, model) or 2x16x16 (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (reduced test meshes, elastic re-meshes).

    Every axis is ``Auto``: placement comes from the sharding rules and
    ``with_sharding_constraint`` hints (``runtime.sharding``), which JAX
    accepts only on Auto axes — ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_shards(mesh) -> int:
    """Number of shards the batch dim is split into (pod x data)."""
    n = mesh.shape.get("data", 1)
    n *= mesh.shape.get("pod", 1)
    return n
