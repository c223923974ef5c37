"""Pure-jnp oracles for the band_reclassify kernels."""
import jax
import jax.numpy as jnp


def multiview_band_reclassify_ref(F, labels, W, b, start_rows, end_rows):
    """Multi-view oracle: every margin of the shared table from one product,
    and view v relabelled on rows [start_rows[v], end_rows[v]) only."""
    k, n = labels.shape
    z = jnp.einsum("kd,nd->kn", W.astype(jnp.float32),
                   F.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) - b[:, None]
    new = jnp.where(z >= 0, 1, -1).astype(jnp.int8)
    rows = jnp.arange(n)[None, :]
    inside = (rows >= start_rows[:, None]) & (rows < end_rows[:, None])
    return jnp.where(inside, new, labels)


def band_reclassify_ref(F_sorted, labels, w, b, start_block, width, *,
                        cap: int, block_n: int):
    n, d = F_sorted.shape
    start = start_block * block_n
    Fb = jax.lax.dynamic_slice(F_sorted, (start, 0), (cap, d))
    eps = jnp.einsum("nd,d->n", Fb.astype(jnp.float32), w.astype(jnp.float32)) - b
    new = jnp.where(eps >= 0, 1, -1).astype(jnp.int8)[:, None]
    old = jax.lax.dynamic_slice(labels, (start, 0), (cap, 1))
    in_band = (jnp.arange(cap) < width)[:, None]
    merged = jnp.where(in_band, new, old)
    return jax.lax.dynamic_update_slice(labels, merged, (start, 0))
