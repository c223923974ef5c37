"""Public wrappers: align band windows to tile boundaries and clamp them."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.band_reclassify.kernel import (
    multiview_band_reclassify as _mv_kernel,
)


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

