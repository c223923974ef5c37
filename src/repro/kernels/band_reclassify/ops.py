"""Public wrappers: align band windows to tile boundaries and clamp them."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.band_reclassify.kernel import (
    band_reclassify as _kernel,
    multiview_band_reclassify as _mv_kernel,
)
from repro.kernels.band_reclassify.ref import band_reclassify_ref  # noqa: F401


def multiview_band_reclassify(F, labels, W, b, start_rows, end_rows, *,
                              block_n: int = 512, interpret: bool = False):
    """Relabel rows [start_rows[v], end_rows[v]) of the shared scratch
    table under each view's model (W[v], b[v]) in ONE kernel launch.

    labels: (k, n) int8, rows aligned to F's row order. The kernel streams
    the union of the k windows, rounded out to block_n tiles, once; a view
    with an empty window (end ≤ start) relabels nothing. A window may span
    the whole table: there is no capacity to truncate it. Returns
    (labels', rows streamed). Where every window is empty the kernel still
    streams one tile and writes it back unchanged."""
    n, d = F.shape
    n_tiles = n // block_n
    start_rows = jnp.clip(jnp.asarray(start_rows, jnp.int32), 0, n)
    end_rows = jnp.clip(jnp.asarray(end_rows, jnp.int32), 0, n)
    has = end_rows > start_rows
    first = jnp.min(jnp.where(has, start_rows // block_n, n_tiles - 1))
    last = jnp.max(jnp.where(has, -(-end_rows // block_n), 0))
    tiles = jnp.stack([first, jnp.maximum(last, first + 1)])
    out = _mv_kernel(F, labels, W, jnp.asarray(b, jnp.float32), tiles,
                     start_rows, end_rows, block_n=block_n,
                     interpret=interpret)
    return out, (tiles[1] - tiles[0]) * block_n


def band_reclassify(F_sorted, labels, w, b, start_row, end_row, *,
                    cap: int = 4096, block_n: int = 512,
                    interpret: bool = False):
    """Relabel rows [start_row, end_row) of the eps-sorted table under (w,b).

    labels: (n,) int8. The window is tile-aligned and capacity-clamped; the
    caller (SKIING driver) must ensure end_row − aligned_start ≤ cap."""
    n, d = F_sorted.shape
    start_row = jnp.asarray(start_row, jnp.int32)
    end_row = jnp.asarray(end_row, jnp.int32)
    start_block = jnp.clip(start_row // block_n, 0,
                           max(0, (n - cap) // block_n))
    width = jnp.clip(end_row - start_block * block_n, 0, cap)
    out = _kernel(F_sorted, labels[:, None], w, jnp.asarray(b, jnp.float32),
                  start_block, width, cap=cap, block_n=block_n,
                  interpret=interpret)
    return out[:, 0]
