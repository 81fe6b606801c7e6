"""Production mesh construction.

Single pod: 16x16 = 256 chips (data, model).
Multi-pod:  2x16x16 = 512 chips (pod, data, model); the "pod" axis is a
second gradient/data-parallel axis whose collectives ride the inter-pod
DCI links.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over however many host devices exist (tests)."""
    n = 1
    for s in shape:
        n *= s
    assert len(jax.devices()) >= n, "not enough host devices; set XLA_FLAGS"
    return _make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
