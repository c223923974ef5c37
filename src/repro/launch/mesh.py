"""Device meshes. Import never touches jax device state — each mesh is
built inside its function."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (sharding left to the compiler
    and to the shard_map specs)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_row_mesh(shards: int, axes=("data", "model")):
    """A (shards, 1) mesh over the first `shards` devices: a table's rows
    cut over `data`, whole rows on each device. With one shard it is
    `make_host_mesh()`'s mesh."""
    import numpy as np
    from jax.sharding import Mesh
    devices = np.array(jax.devices()[:shards]).reshape(shards, 1)
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many (possibly fake) local devices exist —
    used by tests and CPU examples."""
    return make_mesh(shape, axes)
