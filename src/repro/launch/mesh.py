"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to build these meshes on CPU.

Topology note: a v5e pod's ICI is a physical 2-D torus; ``jax.make_mesh``
orders devices so that neighboring mesh coordinates are ICI neighbors —
which is exactly what the paper's switchless-torus schedules
(``repro.core.torus``) assume.
"""
from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, shape=None, axes=None):
    """The pod-scale mesh — validated against the platform's actual device
    count instead of assuming a 256-chip pod.  Pass ``shape=``/``axes=`` for
    a small dev mesh (e.g. ``shape=(1, 8)`` on a forced-8-device CPU)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    elif axes is None:
        axes = ("pod", "data", "model")[-len(tuple(shape)):]
    n = math.prod(shape)
    avail = jax.device_count()
    if n != avail:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {n} devices but the platform "
            f"has {avail}; pass shape=/axes= matching the device count "
            f"(e.g. shape=(1, {avail})), or use make_device_mesh to take a "
            f"submesh of the available devices")
    return make_mesh(shape, axes)


def make_device_mesh(shape, axes, devices=None):
    """Mesh over the *first* ``prod(shape)`` devices.

    Unlike ``jax.make_mesh`` this does not require using every device on the
    platform — the serving engine's ``MeshSpec`` builds small dev meshes
    ((1, 2), (1, 4), ...) on a forced-8-device CPU this way."""
    devices = list(jax.devices() if devices is None else devices)
    n = math.prod(shape)
    if len(devices) < n:
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices; "
                         f"only {len(devices)} available")
    arr = np.asarray(devices[:n]).reshape(tuple(shape))
    return jax.sharding.Mesh(arr, tuple(axes),
                             axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes used for batch/data parallelism, pod-major."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def host_mesh(n: int = 1, model: int = 1):
    """Small local mesh for examples/tests on real CPU devices."""
    return make_mesh((n, model), ("data", "model"))
