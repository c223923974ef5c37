"""Pure-jnp oracle for the band_reclassify kernel."""
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

