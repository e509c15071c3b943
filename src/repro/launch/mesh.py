"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod = (data=16, model=16) = 256 chips;
multi-pod = (pod=2, data=16, model=16) = 512 chips.  When the process has
more placeholder devices than the mesh needs (the dry-run process always
creates 512), the mesh takes a prefix slice.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def _auto_axes(n: int):
    """Auto axis types: the model code places activations with
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    Explicit axes refuse."""
    import jax

    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=_auto_axes(len(shape)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    import jax

    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:n], axis_types=_auto_axes(2))


def dp_size(mesh) -> int:
    return int(mesh.shape.get("data", 1) * mesh.shape.get("pod", 1))
