"""Covertype-shaped entity table (the benchmark's own copy).

UCI Covertype's layout: 10 quantitative attributes, 4 one-hot wilderness
areas and 40 one-hot soil types, 54 columns, and 7 cover types at the
source's class counts. Each class has its own means for the quantitative
attributes and its own wilderness and soil distributions; those class
profiles come from the fixed `profile_seed`, so every run serves the same
geometry, and `--seed` draws the rows. Quantitative attributes are
standardized, and rows are L2-normalized.
"""
from __future__ import annotations

import numpy as np


def make(table: dict, k: int, rng: np.random.Generator, rows: int):
    """-> (F (rows, 54) float32, truth (rows,) int32 in [0, k))."""
    counts = np.asarray(table["class_counts"], np.int64)
    if counts.size != k:
        raise ValueError(f"{counts.size} class counts for k = {k}")
    q, w, s = (int(table[c]) for c in ("quantitative", "wilderness", "soil"))
    prof = np.random.default_rng(int(table["profile_seed"]))
    means = prof.normal(size=(k, q)).astype(np.float32)
    means *= np.float32(table["class_separation"])
    p_wild = prof.dirichlet(np.full(w, 0.5), size=k)
    p_soil = prof.dirichlet(np.full(s, 0.2), size=k)
    # the source's class shares at this row count, largest remainder first
    share = counts * rows / counts.sum()
    per = np.floor(share).astype(np.int64)
    per[np.argsort(per - share)[: rows - per.sum()]] += 1
    truth = rng.permutation(np.repeat(np.arange(k, dtype=np.int32), per))
    F = np.zeros((rows, q + w + s), np.float32)
    F[:, :q] = rng.standard_normal((rows, q), dtype=np.float32)
    F[:, :q] += means[truth]
    u = rng.random((rows, 2))
    for c in range(k):
        idx = np.flatnonzero(truth == c)
        wild = np.searchsorted(np.cumsum(p_wild[c]), u[idx, 0], side="right")
        soil = np.searchsorted(np.cumsum(p_soil[c]), u[idx, 1], side="right")
        F[idx, q + np.minimum(wild, w - 1)] = 1.0
        F[idx, q + w + np.minimum(soil, s - 1)] = 1.0
    F /= np.linalg.norm(F, axis=1, keepdims=True)
    return F, truth
