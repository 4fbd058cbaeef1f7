"""Production mesh definition (TPU v5e) + sweep-mesh helpers.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_sweep_mesh(shape=None):
    """Mesh for sharding a sweep's batch axis over hosts/chips.

    ``shape``: lane counts per mesh axis (e.g. ``(4,)`` or ``(2, 2)``);
    ``None`` uses every visible device as one flat batch axis. Axis
    names are batch axes (no ``model`` axis), so ``batch_axes_of``
    returns all of them.
    """
    if shape is None:
        shape = (jax.device_count(),)
    shape = tuple(int(s) for s in shape)
    axes = ("data",) if len(shape) == 1 else \
        tuple(f"batch{i}" for i in range(len(shape)))
    return jax.make_mesh(shape, axes)


def mesh_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def batch_axes_of(mesh) -> tuple:
    """Mesh axes the batch dim is sharded over."""
    return tuple(a for a in mesh.axis_names if a != "model")


def device_axis_of(mesh) -> str:
    """The single mesh axis the sim's DEVICE dimension shards over.

    Device-axis sharding (``jaxsim.run_device_sharded``) places one
    fleet's per-device state over the mesh, so it needs exactly one
    batch axis to name in its per-event collectives — build the mesh
    with ``make_sweep_mesh((k,))``. Multi-axis meshes are for sweep-axis
    sharding, where lanes never talk to each other.
    """
    axes = batch_axes_of(mesh)
    if len(axes) != 1:
        raise ValueError(
            f"device-axis sharding needs a single batch-axis mesh "
            f"(make_sweep_mesh((k,))); got axes {axes}")
    return axes[0]


def n_lanes(mesh) -> int:
    """Number of shards the batch axis spreads over (1 for mesh=None)."""
    if mesh is None:
        return 1
    out = 1
    for a in batch_axes_of(mesh):
        out *= mesh.shape[a]
    return out


def n_chips(mesh) -> int:
    return mesh.devices.size
