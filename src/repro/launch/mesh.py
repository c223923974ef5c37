"""Production meshes. Import never touches jax device state — the mesh is
built inside the function, per the dry-run contract."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (sharding left to the compiler
    and to the shard_map specs)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(num_devices: int, *, model_parallel: int = 16):
    """Rebuild a mesh from the devices that survive a failure. Keeps the
    model axis (TP degree is a property of the checkpointed layout) and
    shrinks the data axis; restore_checkpoint reshards onto it."""
    devices = jax.devices()[:num_devices]
    assert num_devices % model_parallel == 0, (num_devices, model_parallel)
    data = num_devices // model_parallel
    import numpy as np
    arr = np.array(devices).reshape(data, model_parallel)
    from jax.sharding import Mesh
    return Mesh(arr, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


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
