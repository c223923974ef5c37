"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.eps_affine.ops import eps_affine
from repro.kernels.eps_affine.ref import eps_affine_ref
from repro.kernels.band_reclassify.ops import (band_reclassify,
                                               multiview_band_reclassify)
from repro.kernels.band_reclassify.ref import multiview_band_reclassify_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref

R = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
           dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,d", [(256, 54), (1000, 128), (513, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_eps_affine_sweep(n, d, dtype):
    F = jnp.asarray(R.normal(size=(n, d)), dtype)
    w = jnp.asarray(R.normal(size=d), jnp.float32)
    b = jnp.float32(R.normal())
    eps, lab, cnt = eps_affine(F, w, b, block_n=256, interpret=True)
    eps_r, lab_r, cnt_r = eps_affine_ref(F, w, b)
    np.testing.assert_allclose(np.asarray(eps), np.asarray(eps_r), **_tol(dtype))
    # labels may differ only where eps ~ 0 (dtype rounding at the boundary)
    disagree = np.asarray(lab) != np.asarray(lab_r)
    assert np.all(np.abs(np.asarray(eps_r)[disagree]) < 1e-2)
    assert abs(int(cnt) - int(cnt_r)) <= int(disagree.sum())


@pytest.mark.parametrize("n,d,start,end", [
    (2048, 64, 300, 700), (2048, 64, 0, 1), (2048, 64, 1500, 2048),
    (4096, 200, 100, 4000),
])
def test_band_reclassify_sweep(n, d, start, end):
    F = jnp.asarray(np.sort(R.normal(size=(n, d)), axis=0), jnp.float32)
    labels = jnp.asarray(R.integers(0, 2, n) * 2 - 1, jnp.int8)
    w = jnp.asarray(R.normal(size=d), jnp.float32)
    b = 0.1
    cap = 4096 if end - start > 1024 else 1024
    out = np.asarray(band_reclassify(F, labels, w, b, start, end,
                                     cap=min(cap, n), block_n=256,
                                     interpret=True))
    # oracle: rows in [aligned window ∩ band] relabeled, others untouched
    block_n = 256
    sb = min(max(0, start // block_n), max(0, (n - min(cap, n)) // block_n))
    w0 = sb * block_n
    width = int(np.clip(end - w0, 0, min(cap, n)))
    expect = np.asarray(labels).copy()
    z = np.asarray(F[w0:w0 + width], np.float32) @ np.asarray(w) - b
    expect[w0:w0 + width] = np.where(z >= 0, 1, -1)
    assert np.array_equal(out, expect)


def _multiview_oracle(F, labels, W, b, starts, ends):
    """numpy: view v relabelled on rows [starts[v], ends[v]) from f64
    margins, every other label as it was; also the mask of relabelled
    (view, row) pairs whose margin is too near 0 for f32 to settle."""
    z = np.asarray(W, np.float64) @ np.asarray(F, np.float64).T \
        - np.asarray(b, np.float64)[:, None]
    rows = np.arange(labels.shape[1])[None, :]
    inside = (rows >= np.asarray(starts)[:, None]) & \
             (rows < np.asarray(ends)[:, None])
    expect = np.where(inside, np.where(z >= 0, 1, -1),
                      np.asarray(labels)).astype(np.int8)
    return expect, inside & (np.abs(z) < 1e-3)


def _mv_case(k, n, d):
    F = jnp.asarray(R.normal(size=(n, d)), jnp.float32)
    labels = jnp.asarray(R.integers(0, 2, (k, n)) * 2 - 1, jnp.int8)
    W = jnp.asarray(R.normal(size=(k, d)), jnp.float32)
    b = jnp.asarray(R.normal(size=k), jnp.float32)
    return F, labels, W, b


def _check_mv(F, labels, W, b, starts, ends, block_n):
    starts = np.asarray(starts, np.int32)
    ends = np.asarray(ends, np.int32)
    out, streamed = multiview_band_reclassify(F, labels, W, b, starts, ends,
                                              block_n=block_n, interpret=True)
    out = np.asarray(out)
    expect, near = _multiview_oracle(F, labels, W, b, starts, ends)
    assert np.array_equal(out[~near], expect[~near])
    assert np.isin(out[near], (-1, 1)).all()
    ref = multiview_band_reclassify_ref(F, labels, W, b, jnp.asarray(starts),
                                        jnp.asarray(ends))
    assert np.array_equal(out, np.asarray(ref))
    return out, int(streamed)


@pytest.mark.parametrize("k,n,d", [(4, 2048, 64), (7, 2048, 128), (16, 4096, 32)])
def test_multiview_band_reclassify_sweep(k, n, d):
    """Multi-view kernel == the numpy oracle and the jnp oracle on one
    shared table, with independent per-view windows (some empty); the
    kernel streams the union window rounded out to tiles."""
    F, labels, W, b = _mv_case(k, n, d)
    starts = R.integers(0, n, k)
    ends = np.minimum(starts + R.integers(0, 1500, k), n)
    ends[0] = starts[0]                               # one empty window
    block_n = 256
    _, streamed = _check_mv(F, labels, W, b, starts, ends, block_n)
    has = ends > starts
    first = starts[has].min() // block_n
    last = -(-ends[has].max() // block_n)
    assert streamed == (last - first) * block_n


# (k, d) of the tests' shapes; windows as fractions of the table
MV_SHAPES = [(4, 64), (7, 54), (16, 512)]
MV_WINDOWS = {
    # wider than half the table, one spanning it whole
    "wide": lambda k: [(0.0, 1.0)] + [(0.1 * (v % 3), 0.55 + 0.1 * (v % 4))
                                      for v in range(1, k)],
    # one view empty while the others are wide
    "one_empty": lambda k: [(0.5, 0.5)] + [(0.0, 0.6 + 0.1 * (v % 4))
                                           for v in range(1, k)],
    # every band empty: nothing relabelled
    "all_empty": lambda k: [(0.0, 0.0)] * k,
    # windows ending on the last tile, one of them inside it alone
    "last_tile": lambda k: [(0.99, 1.0)] + [(0.3 + 0.05 * (v % 5), 1.0)
                                            for v in range(1, k)],
    # narrow windows off tile boundaries, away from the table's ends
    "unaligned": lambda k: [(0.3 + 0.013 * v, 0.31 + 0.021 * v)
                            for v in range(k)],
}


@pytest.mark.parametrize("case", sorted(MV_WINDOWS))
@pytest.mark.parametrize("k,d", MV_SHAPES)
def test_multiview_band_reclassify_union_window(k, d, case):
    """Union-window kernel == numpy oracle: each view relabels its own
    window; every label outside it stays byte-identical; the kernel
    streams the union window once (one tile where every band is empty)."""
    n, block_n = 2048, 256
    F, labels, W, b = _mv_case(k, n, d)
    win = np.asarray(MV_WINDOWS[case](k)) * n
    starts, ends = win[:, 0].astype(np.int32), win[:, 1].astype(np.int32)
    out, streamed = _check_mv(F, labels, W, b, starts, ends, block_n)
    rows = np.arange(n)[None, :]
    outside = (rows < starts[:, None]) | (rows >= ends[:, None])
    assert np.array_equal(out[outside], np.asarray(labels)[outside])
    has = ends > starts
    if case == "all_empty":
        assert np.array_equal(out, np.asarray(labels))
        assert streamed == block_n
    else:
        first = starts[has].min() // block_n
        last = -(-ends[has].max() // block_n)
        assert streamed == (last - first) * block_n


def test_multiview_band_reclassify_matches_single_view():
    """k=1 multi-view launch == the original single-view kernel (from a
    tile-aligned start: the single-view kernel relabels from the start of
    the start's tile, the multi-view one from the start itself)."""
    n, d = 2048, 64
    F = jnp.asarray(np.sort(R.normal(size=(n, d)), axis=0), jnp.float32)
    labels = jnp.asarray(R.integers(0, 2, n) * 2 - 1, jnp.int8)
    w = jnp.asarray(R.normal(size=d), jnp.float32)
    single = band_reclassify(F, labels, w, 0.1, 256, 900,
                             cap=1024, block_n=256, interpret=True)
    multi, _ = multiview_band_reclassify(F, labels[None, :], w[None, :],
                                         jnp.asarray([0.1], jnp.float32),
                                         jnp.asarray([256], jnp.int32),
                                         jnp.asarray([900], jnp.int32),
                                         block_n=256, interpret=True)
    assert np.array_equal(np.asarray(single), np.asarray(multi[0]))


@pytest.mark.parametrize("b,s,nq,nkv,hd,bq", [
    (1, 128, 4, 4, 32, 64),     # MHA
    (2, 256, 8, 2, 32, 128),    # GQA 4:1
    (1, 512, 6, 1, 64, 128),    # MQA-ish, 6 heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, nq, nkv, hd, bq, dtype):
    q = jnp.asarray(R.normal(size=(b, s, nq, hd)), dtype)
    k = jnp.asarray(R.normal(size=(b, s, nkv, hd)), dtype)
    v = jnp.asarray(R.normal(size=(b, s, nkv, hd)), dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bq, interpret=True)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,S,nq,nkv,hd,idx", [
    (2, 1024, 8, 2, 32, 700), (1, 512, 4, 4, 64, 0), (2, 2048, 16, 8, 32, 2047),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, S, nq, nkv, hd, idx, dtype):
    q = jnp.asarray(R.normal(size=(b, 1, nq, hd)), dtype)
    K = jnp.asarray(R.normal(size=(b, S, nkv, hd)), dtype)
    V = jnp.asarray(R.normal(size=(b, S, nkv, hd)), dtype)
    out = decode_attention(q, K, V, idx, block_s=256, interpret=True)
    group = nq // nkv
    ref = decode_attention_ref(q[:, 0].reshape(b, nkv, group, hd), K, V,
                               idx).reshape(b, 1, nq, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_matches_model_attention():
    """The pure-jnp chunked attention used in models == flash kernel."""
    from repro.configs import smoke_config
    from repro.models import layers as L
    from repro.models.params import init_params
    cfg = smoke_config("granite-3-2b")
    p = init_params(L.attention_params(cfg), 0)
    x = jnp.asarray(R.normal(size=(2, 128, cfg.d_model)), jnp.float32)
    pos = jnp.arange(128)[None, :]
    y_model = L.causal_attention(p, cfg, x, pos, chunk=64)
    q, k, v = L.project_qkv(p, cfg, x, pos)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    wo = L._pad_wo(p["wo"], cfg.padded_heads)
    y_kernel = jnp.einsum("bshk,hkd->bsd", out, wo)
    np.testing.assert_allclose(np.asarray(y_model, np.float32),
                               np.asarray(y_kernel, np.float32),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("b,s,H,K,chunk", [
    (2, 128, 3, 16, 32), (1, 64, 2, 32, 64), (2, 96, 1, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_kernel_sweep(b, s, H, K, chunk, dtype):
    """WKV6 Pallas kernel vs the exact sequential recurrence oracle.

    Decays drawn from the trained-RWKV regime (per-token log-decay
    -0.01..-1), where the factored intra-chunk form is exact (see
    models/rwkv6.py docstring for the boundary)."""
    from repro.kernels.wkv6.ops import wkv6
    from repro.kernels.wkv6.ref import wkv6_ref
    q = jnp.asarray(R.normal(size=(b, s, H, K)), dtype)
    k = jnp.asarray(R.normal(size=(b, s, H, K)), dtype)
    v = jnp.asarray(R.normal(size=(b, s, H, K)), dtype)
    la = -jnp.exp(jnp.asarray(R.normal(size=(b, s, H, K)) * 0.5 - 2.0,
                              jnp.float32)).astype(dtype)
    u = jnp.asarray(R.normal(size=(H, K)), jnp.float32)
    out = wkv6(q, k, v, la, u, chunk=chunk, interpret=True)
    tr = lambda t: t.astype(jnp.float32).transpose(0, 2, 1, 3)
    ref = wkv6_ref(tr(q), tr(k), tr(v), tr(la), u).transpose(0, 2, 1, 3)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
          dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)


def test_wkv6_kernel_matches_model_path():
    """Kernel == the model's wkv_chunked (deployed training path)."""
    from repro.kernels.wkv6.ops import wkv6
    from repro.models.rwkv6 import wkv_chunked
    b, s, H, K = 2, 64, 2, 16
    r = jnp.asarray(R.normal(size=(b, s, H, K)), jnp.float32)
    k = jnp.asarray(R.normal(size=(b, s, H, K)), jnp.float32)
    v = jnp.asarray(R.normal(size=(b, s, H, K)), jnp.float32)
    la = -jnp.exp(jnp.asarray(R.normal(size=(b, s, H, K)) * 0.5 - 1.0, jnp.float32))
    u = jnp.asarray(R.normal(size=(H, K)), jnp.float32)
    out_k = wkv6(r, k, v, la, u, chunk=16, interpret=True)
    s0 = jnp.zeros((b, H, K, K), jnp.float32)
    out_m, _ = wkv_chunked(r, k, v, la, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               rtol=3e-4, atol=3e-4)
