"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init and only then builds the mesh.

Axes:
  single pod (v5e-256):  (data=16, model=16)
  multi-pod  (2 pods):   (pod=2, data=16, model=16)

``pod`` is an outer data-parallel axis (per-pod DCN-connected replicas);
``data`` carries batch + FSDP weight sharding; ``model`` carries
TP/EP/SP (DESIGN.md §5).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: the sharding rules place arrays with device_put and
    # with_sharding_constraint and let the partitioner propagate the
    # rest.  jax.make_mesh defaults to Explicit axes, under which shardings
    # are part of array types and an op such as the embedding gather
    # (tokens over data, embedding columns over data) has no single
    # output sharding and is refused at trace time.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh over host platform devices (tests)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel (batch) axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
