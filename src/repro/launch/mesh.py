"""Production meshes (assignment brief: 16×16 single pod, 2×16×16 multi-pod).

`make_production_mesh` is a FUNCTION (not a module constant) so importing this
module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / small-host runs / elastic re-shard)."""
    return _mk(tuple(shape), tuple(axes))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices this host actually has."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def make_serve_mesh(n_shards: int = 0,
                    axis: str = "data") -> jax.sharding.Mesh:
    """1-D slot-sharding mesh for the sharded serving engine.

    One shard per device along `axis` (the production mesh's data axis);
    n_shards=0 takes every local device. Built directly (not via make_mesh)
    so a PREFIX of the host's devices can back a smaller serving tier —
    CPU parity tests force 8 fake devices and shard over all of them."""
    import numpy as np
    devs = jax.devices()
    n = n_shards or len(devs)
    assert 1 <= n <= len(devs), (n, len(devs))
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))
