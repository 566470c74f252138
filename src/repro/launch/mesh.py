"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """A ``(data, model)`` mesh over the attached devices: the chips of a
    TPU host, or on the CPU as many XLA host devices
    (``--xla_force_host_platform_device_count``)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def mesh_axes(mesh) -> tuple[tuple[str, ...], str]:
    """(batch/data axes, model axis) for a mesh from make_production_mesh."""
    names = mesh.axis_names
    model = "model" if "model" in names else names[-1]
    batch = tuple(n for n in names if n != model)
    return batch, model
