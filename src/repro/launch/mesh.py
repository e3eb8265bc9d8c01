"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state. The single-pod
mesh is 16x16 = 256 chips (TPU v5e pod); multi-pod adds a leading "pod"
axis (2 pods = 512 chips, pod axis mapped onto DCN).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import numpy as np

#: The chip the production meshes stand for (``jax.Device.device_kind``
#: of a TPU v5e): the dry-runs compile on placeholder host devices and
#: price their roofline terms with this kind's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagation
    decides placements; this JAX defaults new meshes to ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}; "
            "the dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return _make_mesh(shape, axes, devices[:need])


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for tests (requires >= n_data*n_model host devices)."""
    need = n_data * n_model
    devices = jax.devices()[:need]
    return _make_mesh((n_data, n_model), ("data", "model"), devices)


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-parallel axes of a mesh (pod-major when present)."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    s = 1
    for n in names:
        if n in mesh.axis_names:
            s *= mesh.shape[n]
    return s
