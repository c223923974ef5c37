"""What an exact band relabel must read: the band kernel's roofline need.

A maintenance round may change the label of a (row, view) pair only where
the row's stored margin lies in the view's Lemma 3.1 band [lw_v, hw_v).
An exact relabel therefore has to read each row of the union over views
of those bands once (d float32 values) and read and write its k labels,
whatever kernel does it. `union_band_rows` counts those rows on the
device, from the stored margins that the benchmark recomputes under the
reference's stored model; `need_seconds` turns a count into time at the
chip's peak HBM bandwidth. The count runs after the traced window has
closed, so no trace sum counts its device work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference


@jax.jit
def union_band_rows(F, real, W_s, b_s, lw, hw):
    """Rows (real == True) with some view's stored margin F . W_s - b_s in
    [lw_v, hw_v). F: (n, d); real: (n,) bool; W_s: (k, d); b_s, lw, hw: (k,)."""
    eps = jnp.einsum("nd,kd->kn", F, W_s,
                     precision=jax.lax.Precision.HIGHEST) - b_s[:, None]
    band = reference.in_band(eps, lw[:, None], hw[:, None])
    return jnp.sum(jnp.any(band, axis=0) & real)


def need_bytes(rows: int, d: int, k: int) -> int:
    """Each row of the union read once (d f32), its k int8 labels read and
    written."""
    return rows * (4 * d + 2 * k)


def need_seconds(rows: int, d: int, k: int, peaks: dict) -> float:
    return need_bytes(rows, d, k) / float(peaks["hbm_bytes_per_s"])
