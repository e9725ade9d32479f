"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init and then calls these.

Mesh axes:
* ``data``  — batch (and, for decode cells, KV-batch) sharding
* ``model`` — tensor/sequence sharding, the axis the paper's dataflow
  choice plays out on (layer-by-layer ↔ TP gathers; fused ↔ sequence
  sharding with local halos)
* ``pod``   — the multi-pod outer data axis (2 pods × 256 chips)

Every mesh the repo builds comes from ``make_mesh`` and has Auto axis
types: shardings propagate from the arguments' ``NamedSharding`` and
``shard_map`` bodies run without a mesh context (``jax.make_mesh``
defaults to Explicit axes, which would demand one).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None) -> jax.sharding.Mesh:
    """Auto-typed mesh over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes a global batch is sharded over (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"
