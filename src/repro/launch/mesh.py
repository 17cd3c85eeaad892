"""Production mesh construction.

A function (not a module constant) so importing this module never touches
jax device state. Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips; the leading "pod" axis is pure data
parallelism across pods (ICI within a pod, DCI across).
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(n, 512)} (dryrun.py sets this automatically)")
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, axes)


def make_slab_mesh(n_slabs: int | None = None, axis: str = "data"):
    """1-D slab mesh for the sharded-scene subsystem (``core/shards.py``).

    Defaults to one slab per local device; asks for the
    ``xla_force_host_platform_device_count`` escape hatch when more slabs
    than devices are requested (CPU CI runs the mesh paths under 8 forced
    host devices — see scripts/ci.sh).
    """
    import jax
    from jax.sharding import AxisType

    devs = jax.devices()
    n = int(n_slabs) if n_slabs else len(devs)
    if n > len(devs):
        raise RuntimeError(
            f"need {n} devices for a {n}-slab mesh, have {len(devs)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n}")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devs[:n])


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests."""
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(shape))
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev_array, axes)
