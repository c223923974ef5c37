"""Citeseer-shaped entity table with k topic classes (the benchmark's own copy).

Each row is one abstract: `words_per_row` hashed word buckets with
exponential weights (the hashing trick into `hash_dim` dense columns), plus
the row's topic words. Topic c owns its own `topic_columns` columns, so
every one-vs-all view has a boundary that runs through the table. A
`label_noise` share of rows carries another topic as its truth, as real
labels do. Rows are L1-normalized.

Classes are dealt round-robin and then shuffled, so every seed gives every
topic the same number of rows; the seed changes which rows and which words.
The table is built in float32 throughout, with no float64 intermediate.
"""
from __future__ import annotations

import numpy as np


def make(table: dict, k: int, rng: np.random.Generator, rows: int):
    """-> (F (rows, hash_dim) float32, truth (rows,) int32 in [0, k))."""
    d = int(table["hash_dim"])
    words = int(table["words_per_row"])
    t = int(table["topic_columns"])
    if k * t > d:
        raise ValueError(f"{k} topics x {t} columns exceed {d} columns")
    topic = rng.permutation(np.arange(rows, dtype=np.int32) % k)
    F = np.zeros((rows, d), np.float32)
    cols = rng.integers(0, d, size=(rows, words), dtype=np.int32)
    vals = rng.standard_exponential(size=(rows, words), dtype=np.float32)
    np.put_along_axis(F, cols, vals, axis=1)
    tcols = topic[:, None] * t + np.arange(t, dtype=np.int32)[None, :]
    tvals = rng.standard_exponential(size=(rows, t), dtype=np.float32)
    tvals *= np.float32(table["topic_weight"])
    F[np.arange(rows)[:, None], tcols] += tvals   # no (row, col) repeats
    F /= F.sum(axis=1, keepdims=True, dtype=np.float32)
    truth = topic.copy()
    flip = rng.random(rows, dtype=np.float32) < np.float32(table["label_noise"])
    truth[flip] = rng.integers(0, k, int(flip.sum()), dtype=np.int32)
    return F, truth
