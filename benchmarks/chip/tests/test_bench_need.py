"""The band kernel's need count against a brute-force count, and its
roofline share on one chip and on four."""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import need
import reference
import run


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


@pytest.mark.parametrize("chips,want", [
    # rounds needing 8 and 4 ms of one chip's HBM time, launches of 12 ms
    # on average (36 ms over 3): 6 / 12 = 50%
    (1, 50.0),
    # the same rounds on four chips that share the table: each chip's
    # share of a round needs 1.5 ms against its 12 ms launch
    (4, 12.5),
])
def test_band_kernel_roofline(chips, want):
    reader = run.module_from(run.HERE / "layers" / "band_kernel_roofline.py")
    stub = SimpleNamespace(cell={"chips": chips}, launch_need_s=[8e-3, 4e-3],
                           trace={"kernel_s": 36e-3, "kernel_launches": 3})
    assert reader.read(stub) == pytest.approx(want)
    assert reader.read(SimpleNamespace(cell={"chips": chips}, trace=None,
                                       launch_need_s=[8e-3])) is None
