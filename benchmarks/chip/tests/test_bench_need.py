"""The band kernel's need count against a brute-force count."""
import jax.numpy as jnp
import numpy as np

import need
import reference


def test_union_band_rows_matches_brute_force():
    rng = np.random.default_rng(3)
    n, d, k = 500, 16, 6
    F = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(k, d)).astype(np.float32)
    b = rng.normal(size=k).astype(np.float32)
    lw = -rng.random(k).astype(np.float32)
    hw = rng.random(k).astype(np.float32)
    real = rng.random(n) < 0.9
    got = int(need.union_band_rows(
        jnp.asarray(F), jnp.asarray(real), jnp.asarray(W), jnp.asarray(b),
        jnp.asarray(lw), jnp.asarray(hw)))
    count = 0
    for i in range(n):
        if not real[i]:
            continue
        z = reference.margins(F[i:i + 1], W, b)[0]
        count += any(lw[v] <= z[v] < hw[v] for v in range(k))
    assert got == count
    assert 0 < count < real.sum()


def test_need_bytes_and_seconds():
    assert need.need_bytes(10, 4096, 16) == 10 * (4096 * 4 + 32)
    peaks = {"hbm_bytes_per_s": 819e9}
    assert np.isclose(need.need_seconds(1000, 1024, 16, peaks),
                      1000 * 4128 / 819e9)
