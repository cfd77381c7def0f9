"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests and benches see the single real CPU device.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples (same axis names)."""
    return _make_mesh((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod composes with data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
