"""Mesh construction (TPU v5e target).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
ADSP worker axis for replica-heavy architectures (cross-pod links are the
slow/heterogeneous resource ADSP's commit schedule protects).

Every mesh in the repo comes from ``make_mesh``: its axes are GSPMD
(``AxisType.Auto``), which the logical-axis constraints of
``models.layers.annotate`` and the partially manual ADSP ``shard_map``
are written for. ``jax.make_mesh``'s own default (``Explicit`` axes)
turns those constraints into assertions.

Functions, not module constants — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

from repro.ps.train_step import worker_axes_for  # canonical home moved to ps

__all__ = ["make_mesh", "make_production_mesh", "worker_axes_for", "WORKER_AXES"]

WORKER_AXES = {"single": ("data",), "multi": ("pod", "data")}


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """A mesh with GSPMD (Auto) axes over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

