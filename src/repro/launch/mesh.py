"""Mesh construction — the one place the repo builds a device mesh.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because the dry-run pins the device
count via XLA_FLAGS before any jax call, while tests/benches must keep the
default single device.

Every axis is ``AxisType.Auto``: sharding is decided by the partition
rules and ``with_sharding_constraint`` (GSPMD) and by the explicit
shard_map kernels (DESIGN §8).  ``jax.make_mesh`` alone makes Explicit
axes, under which those constraints and plain array indexing raise.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str] = ("data", "model"),
              *, devices: Optional[Sequence] = None):
    """Mesh of ``shape`` over ``axes`` (all Auto), on ``devices`` or the
    first ``prod(shape)`` visible devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod topology: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (DP / FSDP), ``model`` (TP / EP); ``pod`` is the DCI-
    connected data-parallel axis added in the multi-pod configuration.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Degenerate mesh over the locally visible devices (tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n // model_axis, model_axis))
