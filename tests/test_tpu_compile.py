"""Compile-only checks of the device path for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it refuses
what the chip would refuse (block shapes off the (8, 128) tiling, more VMEM
than a kernel may use, a program larger than device memory). These tests
compile the band kernel and the sharded engine's jitted steps at the
paper's table sizes for one described v5e device. Nothing runs.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers import every test file.
"""
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (AxisType, Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P)

from repro.core.sharded import (ShardedMultiViewHazy, kernel_interpret,  # noqa: E402
                                multiview_state_specs)
from repro.kernels.band_reclassify.ops import multiview_band_reclassify  # noqa: E402

V5E_HBM_BYTES = 16e9
# (n real rows, d, k): Citeseer at the hashed width chip_smoke.py serves,
# Forest's 54 dense features, and the chip benchmark's two configurations
# (benchmarks/chip/configs: citeseer-k16, covtype-k7)
SHAPES = {"citeseer": (721_000, 1024, 16), "forest": (582_000, 54, 16),
          "citeseer-k16": (400_000, 4096, 16), "covtype-k7": (581_012, 54, 7)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def four_chip_mesh(topo):
    """The (4, 1) row mesh an `engine = sharded, shards = 4` view takes on
    a v5e 2x2 host."""
    return Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _driver(mesh, name):
    n, d, k = SHAPES[name]
    return ShardedMultiViewHazy(mesh=mesh, n=n, d=d, k=k, M=1.0)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _entry_ops_of_shape(text: str, shape: str) -> list:
    """The instructions of the compiled program's ENTRY computation whose
    result is an array of `shape` (e.g. `f32[721408,1024]`), parameters
    left out."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    want = re.compile(r"^\s*(?:ROOT )?%\S+ = " + re.escape(shape)
                      + r"\{[^}]*\} ([\w-]+)\(")
    return [line.strip() for line in entry.splitlines()
            if (m := want.match(line)) and m.group(1) != "parameter"]


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_band_kernel_compiles_for_v5e(one_chip_mesh, name):
    """The band kernel alone, at the padded table size and tiling the
    engine derives for one chip; the kernel can take the whole shard. Its
    op keeps the name the chip benchmark's trace reduction looks for."""
    dr = _driver(one_chip_mesh, name)
    assert dr.n_pad >= dr.n and dr.n_pad % dr.block_n == 0
    assert dr.block_n % 128 == 0 and dr.cap == dr.n_pad
    d, k = dr.d, dr.k
    rep = NamedSharding(one_chip_mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    fn = jax.jit(lambda F, L, W, b, s, e: multiview_band_reclassify(
        F, L, W, b, s, e, block_n=dr.block_n))
    compiled = fn.lower(sds((dr.n_pad, d), jnp.float32),
                        sds((k, dr.n_pad), jnp.int8),
                        sds((k, d), jnp.float32), sds((k,), jnp.float32),
                        sds((k,), jnp.int32), sds((k,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%multiview_band_reclassify" in text
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("step", ["update", "reorganize"])
def test_multiview_steps_compile_for_v5e(one_chip_mesh, step):
    """The engine's jitted maintenance steps at Citeseer size on a
    one-device mesh: the update step launches the compiled kernel (never
    the interpreter on a TPU mesh) and returns only labels, not a copy of
    the table; both fit one chip's memory."""
    assert kernel_interpret(one_chip_mesh) is False
    dr = _driver(one_chip_mesh, "citeseer")
    state = multiview_state_specs(dr.n_pad, dr.d, dr.k, one_chip_mesh)
    rep = NamedSharding(one_chip_mesh, P())
    W = jax.ShapeDtypeStruct((dr.k, dr.d), jnp.float32, sharding=rep)
    b = jax.ShapeDtypeStruct((dr.k,), jnp.float32, sharding=rep)
    fn = dr._update if step == "update" else dr._reorg
    compiled = fn.lower(state, W, b).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    text = compiled.as_text()
    if step == "update":
        assert "tpu_custom_call" in text
        table_bytes = dr.n_pad * dr.d * 4
        assert compiled.memory_analysis().output_size_in_bytes < table_bytes
    else:
        # the table moves in one pass: the row gather is the one
        # table-shaped instruction, with no fill select after it
        table = _entry_ops_of_shape(text, f"f32[{dr.n_pad},{dr.d}]")
        assert len(table) == 1 and "gather" in table[0], table


def test_probe_step_reads_one_row_for_v5e(one_chip_mesh):
    """The point read's probe program at the chip benchmark's Citeseer
    size reads the eps-map and the entity's one feature row: a few tens of
    MB, not the 6.55 GB table."""
    dr = _driver(one_chip_mesh, "citeseer-k16")
    state = multiview_state_specs(dr.n_pad, dr.d, dr.k, one_chip_mesh)
    rep = NamedSharding(one_chip_mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    compiled = dr._probe.lower(state, sds((dr.k, dr.d), jnp.float32),
                               sds((dr.k,), jnp.float32),
                               sds((), jnp.int32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    table_bytes = dr.n_pad * dr.d * 4
    assert cost["bytes accessed"] < table_bytes / 100


@pytest.mark.parametrize("step", ["update", "reorganize"])
def test_four_chip_steps_compile_for_v5e_2x2(four_chip_mesh, step):
    """The whole Citeseer table (the benchmark's `citeseer-h4096`: 721,000
    x 4,096 f32, k = 16) on four described chips: 180,352 rows a chip.
    The update step launches the kernel on each shard and ends in one
    all-reduce, of the 16 band widths and the 4 shards' streamed rows;
    the reorganize crosses no chip and writes a shard's rows once, by its
    row gather. With reorganize's copy a chip holds under 6 GB of the
    table, well inside its memory."""
    dr = ShardedMultiViewHazy(mesh=four_chip_mesh, n=721_000, d=4096, k=16,
                              M=1.0)
    assert dr.shards == 4 and dr.n_pad == 4 * 180_352
    state = multiview_state_specs(dr.n_pad, dr.d, dr.k, four_chip_mesh)
    rep = NamedSharding(four_chip_mesh, P())
    W = jax.ShapeDtypeStruct((dr.k, dr.d), jnp.float32, sharding=rep)
    b = jax.ShapeDtypeStruct((dr.k,), jnp.float32, sharding=rep)
    fn = dr._update if step == "update" else dr._reorg
    compiled = fn.lower(state, W, b).compile()
    text = compiled.as_text()
    crossing = [line for line in text.splitlines()
                if any(f" {op}(" in line for op in (
                    "all-reduce", "all-gather", "all-to-all",
                    "collective-permute"))]
    shard_bytes = 180_352 * dr.d * 4
    assert _device_bytes(compiled) < 2.1 * shard_bytes
    if step == "update":
        assert "tpu_custom_call" in text
        assert len(crossing) == 1 and "s32[20]" in crossing[0], crossing
    else:
        assert not crossing, crossing[:2]
        table = _entry_ops_of_shape(text, f"f32[180352,{dr.d}]")
        assert len(table) == 1 and "gather" in table[0], table
