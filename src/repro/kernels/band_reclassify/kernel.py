"""band_reclassify Pallas kernel — the paper's incremental step as a kernel.

Only tiles of the band's window are streamed HBM→VMEM (tile-granular
version of "read only the B+-tree range"). The kernel walks the shard's
tiles with its index maps clamped into the union of the k views' windows,
so each tile there is read once for all k views and nothing outside it is
read. Labels are updated in place via input/output aliasing — rows of a
streamed tile outside a view's window are preserved with a predicated
merge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mv_band_kernel(tiles_ref,          # (2,) i32 SMEM: union window tiles
                    w_ref,              # (k, d) every view's model
                    b_ref,              # (k, 1) f32
                    start_ref, end_ref,  # (k, 1) i32 per-view row windows
                    f_ref, lab_in_ref, lab_out_ref):
    i = pl.program_id(0)

    # a step outside the union window repeats its neighbour's block index:
    # no DMA, no compute, and the output block stays as that step left it
    @pl.when((i >= tiles_ref[0]) & (i < tiles_ref[1]))
    def _():
        z = jax.lax.dot_general(
            w_ref[...].astype(jnp.float32), f_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) - b_ref[...]
        rows = i * f_ref.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, z.shape, 1)
        inside = (rows >= start_ref[...]) & (rows < end_ref[...])
        # select in int32: Mosaic cannot relayout an int8-typed mask
        new = jnp.where(z >= 0, 1, -1)
        old = lab_in_ref[...].astype(jnp.int32)
        lab_out_ref[...] = jnp.where(inside, new, old).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def multiview_band_reclassify(F, labels, W, b, tiles, starts, ends, *,
                              block_n: int = 512, interpret: bool = False):
    """Union-window relabel for k views over ONE shared scratch table.

    F: (n, d) — the shared clustered scratch table; labels: (k, n) int8,
    aligned to F's row order, updated in place; W: (k, d); b: (k,);
    tiles: (2,) i32 — the union window [tiles[0], tiles[1]) in units of
    block_n rows, non-empty; starts/ends: (k,) i32 — view v relabels rows
    [starts[v], ends[v]) only, all of them inside the union window.

    Grid is (n // block_n,), one step a tile. The index maps clamp every
    step into the union window, so the steps before and after it repeat
    the window's first or last block: the pipeline fetches nothing for
    them and writes nothing back until the block index changes. HBM
    traffic is the union window's rows once, whatever k. A step in the
    window computes all k margins of its tile in one MXU product at f32
    precision and relabels view v where the row lies in v's window;
    every other label of the tile is written back as it was read.

    Block layouts follow the TPU tiling rule (the last two block dims are
    multiples of (8, 128) or the whole array dims): W, b and the per-view
    windows are whole-array blocks, labels travel as (k, block_n) tiles,
    so block_n must be a multiple of 128 on TPU."""
    k, n = labels.shape
    n2, d = F.shape
    assert n == n2 and n % block_n == 0

    def tile(i, t):
        return jnp.minimum(jnp.maximum(i, t[0]), t[1] - 1)

    whole = lambda shape: pl.BlockSpec(shape, lambda i, t: (0, 0))
    lab_spec = pl.BlockSpec((k, block_n), lambda i, t: (0, tile(i, t)))
    col = lambda x, dt: x.astype(dt).reshape(k, 1)
    return pl.pallas_call(
        _mv_band_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_n,),
            in_specs=[
                whole((k, d)), whole((k, 1)), whole((k, 1)), whole((k, 1)),
                pl.BlockSpec((block_n, d), lambda i, t: (tile(i, t), 0)),
                lab_spec,
            ],
            out_specs=lab_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.int8),
        input_output_aliases={6: 0},
        interpret=interpret,
    )(tiles.astype(jnp.int32), W, col(b, jnp.float32),
      col(starts, jnp.int32), col(ends, jnp.int32), F, labels)
