"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes (data, model).  Multi-pod: 2 pods = 512 chips, axes (pod, data,
model) — the "pod" axis is extra data parallelism whose collectives cross
the inter-pod (DCI) links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which only names Auto axes (the
    default since JAX 0.7 is Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # CI/test hook: scale the mesh down (e.g. REPRO_MESH_SINGLE=2,4).
    import os

    env = os.environ.get("REPRO_MESH_MULTI" if multi_pod else "REPRO_MESH_SINGLE")
    if env:
        shape = tuple(int(x) for x in env.split(","))
        assert len(shape) == len(axes), (shape, axes)
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices this process has."""
    return make_mesh((data, model), ("data", "model"))


def make_serve_mesh(spec):
    """Mesh for ``ServeConfig.mesh``: ((axis, size), ...) pairs.

    Unlike ``jax.make_mesh`` this tolerates *more* local devices than the
    mesh needs — it lays the mesh over the first prod(sizes) devices — so
    a (1, 1) parity cell runs on a laptop and a (2, 4) cell on the same
    8-virtual-device process as an (8, 1) one.
    """
    import numpy as np
    from jax.sharding import Mesh

    names = tuple(a for a, _ in spec)
    sizes = tuple(int(n) for _, n in spec)
    need = int(np.prod(sizes))
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"mesh {spec!r} needs {need} devices, have {len(devices)}")
    return Mesh(np.array(devices[:need]).reshape(sizes), names)
