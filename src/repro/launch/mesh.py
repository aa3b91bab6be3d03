"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run entry point force-creates 512 host
devices BEFORE calling this.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is an
extra data-parallel (FSDP) dimension over the slower inter-pod links --
gradient reduction over it is the target of the int8-compression option.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the model code places values
    with ``with_sharding_constraint``, which is an assert (not a placement)
    on the ``Explicit`` axes ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices exist (tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch (everything except model)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_shards(mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out
