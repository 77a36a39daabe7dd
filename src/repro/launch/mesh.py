"""Production mesh construction.

A function, not a module-level constant — importing this module never touches
jax device state.  The production target is TPU v5e pods: 16x16 = 256 chips
per pod, 2 pods = 512 chips for the multi-pod dry-run.
"""
from __future__ import annotations

import jax

POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def serving_mesh(n_devices: int = 0, axis: str = "model") -> jax.sharding.Mesh:
    """THE serving-engine mesh: a 1-D tensor-parallel mesh of ``n_devices``
    on the ``axis`` axis (default "model" — the axis the sharding rules map
    heads / kv_heads / ff / vocab / experts to).

    One definition on purpose, so engine, tests and benchmarks build
    identical meshes — and the ProgramStore's mesh-shape key
    (``axis=size``) can never drift between producers.  ``n_devices`` <= 0
    means "every visible device".
    """
    n = n_devices if n_devices > 0 else len(jax.devices())
    assert n <= len(jax.devices()), (n, len(jax.devices()))
    return jax.make_mesh((n,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            n *= mesh.shape[ax]
    return n
