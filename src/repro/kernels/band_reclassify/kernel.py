"""band_reclassify Pallas kernel — the paper's incremental step as a kernel.

Only tiles overlapping the water band [start, start+width) are streamed
HBM→VMEM: the grid covers a fixed `cap`-row window and the scalar-prefetch
`start_block` shifts every tile's index map, so HBM traffic is ∝ band size,
not N (tile-granular version of "read only the B+-tree range"). Labels are
updated in place via input/output aliasing — out-of-band rows inside the
window are preserved with a predicated merge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _band_kernel(scalars_ref,           # (2,) i32: [start_block, width]
                 w_ref, b_ref, f_ref, lab_in_ref, lab_out_ref):
    i = pl.program_id(0)
    width = scalars_ref[1]
    bn = f_ref.shape[0]
    f = f_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    eps = jnp.sum(f * w, axis=1, keepdims=True) - b_ref[0, 0]
    new = jnp.where(eps >= 0, 1, -1).astype(jnp.int8)
    offs = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    in_band = offs < width
    lab_out_ref[...] = jnp.where(in_band, new, lab_in_ref[...])


def _mv_band_kernel(scalars_ref,        # (2, k) i32: [start_block_v; width_v]
                    b_ref,              # (1, k) f32 in SMEM
                    w_ref,              # (k, d) — every view's model, one block
                    f_ref, lab_in_ref, lab_out_ref):
    v = pl.program_id(0)
    i = pl.program_id(1)
    width = scalars_ref[1, v]
    bn = f_ref.shape[0]
    f = f_ref[...].astype(jnp.float32)
    w = w_ref[pl.ds(v, 1), :].astype(jnp.float32)
    eps = jnp.sum(f * w, axis=1)[None, :] - b_ref[0, v]
    # select in int32: Mosaic cannot relayout an int8-typed (1, bn) mask
    new = jnp.where(eps >= 0, 1, -1)
    offs = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    old = lab_in_ref[...].astype(jnp.int32)
    lab_out_ref[...] = jnp.where(offs < width, new, old).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("cap", "block_n", "interpret"))
def multiview_band_reclassify(F, labels, W, b, start_blocks, widths, *,
                              cap: int = 4096, block_n: int = 512,
                              interpret: bool = False):
    """Union-band relabel for k views over ONE shared scratch table.

    F: (n, d) — the shared eps-clustered scratch table (one clustering for
    all views, the multi-view engine's shared-table layout); labels:
    (k, n) int8, row v aligned to the SAME row order as F, updated in
    place; W: (k, d); b: (k,); start_blocks/widths: (k,) i32 — per-view
    windows in units of block_n rows.

    Block layouts follow the TPU tiling rule (the last two block dims are
    multiples of (8, 128) or the whole array dims): W is one whole (k, d)
    block read at row v, b sits in SMEM, and labels travel as (k, 1, n)
    with (1, block_n) tiles, so block_n must be a multiple of 128 on TPU.

    Grid is (k, cap // block_n): program (v, i) streams the i-th tile of
    view v's window and relabels it under view v's model. Each view's
    window must COVER its true eps band in the shared order — relabeling a
    superset is exact, because relabeling recomputes sign(w_v·f − b_v),
    the correct current label for ANY row; the band only bounds which rows
    may have changed. Per-view windows are positioned independently via
    the scalar-prefetch starts, so one launch touches the union of the k
    (covering) bands — HBM traffic ∝ Σ_v window_v, not k·n."""
    k, n = labels.shape
    n2, d = F.shape
    assert n == n2 and cap % block_n == 0 and n % block_n == 0
    grid = (k, cap // block_n)
    scalars = jnp.stack([start_blocks.astype(jnp.int32),
                         widths.astype(jnp.int32)])

    lab_spec = pl.BlockSpec((pl.squeezed, 1, block_n),
                            lambda v, i, s: (v, 0, s[0, v] + i))
    out = pl.pallas_call(
        _mv_band_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((k, d), lambda v, i, s: (0, 0)),
                pl.BlockSpec((block_n, d), lambda v, i, s: (s[0, v] + i, 0)),
                lab_spec,
            ],
            out_specs=lab_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((k, 1, n), jnp.int8),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(scalars, b.reshape(1, -1).astype(jnp.float32), W, F,
      labels.reshape(k, 1, n))
    return out.reshape(k, n)


@functools.partial(jax.jit, static_argnames=("cap", "block_n", "interpret"))
def band_reclassify(F_sorted, labels, w, b, start_block, width, *,
                    cap: int = 4096, block_n: int = 512,
                    interpret: bool = False):
    """F_sorted: (n, d); labels: (n, 1) int8 (updated in place);
    start_block: () i32 — band start in units of block_n rows;
    width: () i32 — band rows counted from the window start.

    Returns updated labels. HBM reads: cap rows of F + cap labels only."""
    n, d = F_sorted.shape
    assert cap % block_n == 0 and n % block_n == 0
    grid = (cap // block_n,)
    scalars = jnp.stack([start_block.astype(jnp.int32), width.astype(jnp.int32)])

    out = pl.pallas_call(
        _band_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, d), lambda i, s: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, s: (0, 0)),
                pl.BlockSpec((block_n, d), lambda i, s: (s[0] + i, 0)),
                pl.BlockSpec((block_n, 1), lambda i, s: (s[0] + i, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, 1), lambda i, s: (s[0] + i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int8),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(scalars, w[None, :], b.reshape(1, 1), F_sorted, labels)
    return out
