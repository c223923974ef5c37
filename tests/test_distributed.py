"""The sharded device engine on meshes of fake CPU devices, each test in a
subprocess: consistency with a numpy relabel and with the host engine,
and the reorganize's shard-local clustering."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600, env=env)
    if (out.returncode == -11 and not out.stderr.strip()
            and not os.environ.get("REPRO_STRICT_SUBPROCESS")):
        # XLA CPU segfault compiling large programs on fake-device meshes:
        # a jaxlib/kernel interaction on some hosts, not a property of the
        # code under test (see ROADMAP open items). Set
        # REPRO_STRICT_SUBPROCESS=1 to turn these skips into failures.
        pytest.skip("jaxlib segfault (SIGSEGV) in XLA compile on this host")
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Sharded hazy engine on a real (fake-device) mesh
# ---------------------------------------------------------------------------

def test_sharded_hazy_multidevice_consistency():
    """One view (k = 1) of the sharded engine on a (4, 2) mesh, trained by
    a forest_like SGD stream: its labels (in the shared clustering order,
    through `real_rows`) and its member count equal sign(F·w − b) under
    the last model."""
    out = _run_subprocess("""
        import numpy as np
        from repro.core.sharded import ShardedMultiViewHazy
        from repro.core import zero_model, sgd_step
        from repro.core.waters import holder_M
        from repro.data import forest_like, example_stream
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        corpus = forest_like(scale=0.01)
        n = (corpus.features.shape[0] // 8) * 8
        F = np.ascontiguousarray(corpus.features[:n, :52])
        sh = ShardedMultiViewHazy(mesh=mesh, n=n, d=52, k=1,
                                  M=holder_M(F, 2.0), p=2.0)
        state = sh.init_state(F)
        model = zero_model(52)
        stream = example_stream(corpus, seed=3, label_noise=0.0)
        for _, f, y in [next(stream) for _ in range(400)]:
            model = sgd_step(model, f[:52], y, lr=0.02, l2=1e-3)
            state = sh.apply_models(state, model.w[None, :],
                                    np.asarray([model.b]))
        truth = np.where(F @ model.w - model.b >= 0, 1, -1)
        gids, labels, _ = sh.real_rows(state)
        assert np.array_equal(np.sort(gids), np.arange(n))
        assert np.array_equal(truth[gids], labels[0])
        assert np.array_equal(sh.all_members(state), [(truth == 1).sum()])
        assert sh.kernel_rounds > 0
        print("OK reorgs=", sh.skiing.reorgs)
    """)
    assert "OK" in out


_MULTIVIEW_VS_HOST = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.sharded import PAD_GID, ShardedMultiViewHazy
        from repro.core.multiview import MultiViewEngine
        from repro.core.waters import holder_M
        from repro.data import cora_like, multiclass_example_stream
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(MESH_SHAPE, ("data", "model"))
        c = cora_like(scale=0.8)
        n, k = N_ROWS, c.num_classes
        F = np.ascontiguousarray(c.features[:n]); d = F.shape[1]
        host = MultiViewEngine(F, k, p=2.0, q=2.0, cost_mode="modeled")
        sh = ShardedMultiViewHazy(mesh=mesh, n=n, d=d, k=k,
                                  M=holder_M(F, 2.0), p=2.0)
        state = sh.init_state(F)
        W = np.zeros((k, d), np.float32); b = np.zeros(k, np.float64)
        lr, l2 = 0.1, 1e-4
        stream = multiclass_example_stream(c, seed=11)
        for i, cls in (next(stream) for _ in range(N_EXAMPLES)):
            if i >= n:
                continue
            f = F[i]
            y = np.where(np.arange(k) == cls, 1.0, -1.0)
            z = W @ f - b.astype(np.float32)
            g = np.where(y * z.astype(np.float64) < 1.0, -y, 0.0)
            W = W * (1.0 - lr * l2)
            W -= (lr * g).astype(np.float32)[:, None] * f[None, :]
            b = b - lr * (-g)
            host.apply_models(W, b)
            state = sh.apply_models(state, W, b)
        # labels: sharded rows live in the shared clustering order (gids);
        # scatter the host's per-view eps order back to entity order first
        gids, labels, _ = sh.real_rows(state)
        assert np.array_equal(np.sort(gids), np.arange(n))   # no padding
        pad = np.asarray(state.gids) == PAD_GID
        assert pad.sum() == sh.n_pad - n
        assert not np.asarray(state.labels)[:, pad].any()
        host_full = np.empty((k, n), np.int8)
        for v in range(k):
            host_full[v, host.perm[v]] = host.labels_sorted[v]
        assert np.array_equal(labels, host_full[:, gids])
        counts = sh.all_members(state)
        assert np.array_equal(counts, host.all_members()), counts
        assert counts.min() > 0 and counts.max() < n   # non-degenerate views
        assert sh.skiing.reorgs >= 1
        assert sh.skiing.total_incremental > 0   # kernel rounds did real work
        # §3.5.2 hybrid probe: device-side waters short-circuit (zero feature
        # bytes) + one shared feature-row gather for the views that miss —
        # must agree with the host labels for every sampled entity
        resolved_total = 0
        for i in range(0, n, 61):
            lab, resolved = sh.hybrid_labels_of(state, int(i))
            assert np.array_equal(lab, host_full[:, i]), (i, lab)
            resolved_total += int(resolved.sum())
        assert resolved_total > 0      # the waters tier did real work
        print("OK reorgs=", sh.skiing.reorgs, "overflows=", sh.overflows,
              "counts=", counts, "water_resolved=", resolved_total)
"""


def _sharded_multiview_vs_host(mesh_shape, n_rows, n_examples) -> str:
    """Run `_MULTIVIEW_VS_HOST` on a mesh of fake CPU devices: the stream's
    first `n_examples` draws train the views (those past `n_rows` are
    skipped)."""
    return _run_subprocess(_MULTIVIEW_VS_HOST
                           .replace("MESH_SHAPE", repr(mesh_shape))
                           .replace("N_ROWS", str(n_rows))
                           .replace("N_EXAMPLES", str(n_examples)))


def test_sharded_multiview_multidevice_consistency():
    """k one-vs-all views over ONE shared scratch table on a (4, 2) mesh,
    maintained through the `multiview_band_reclassify` kernel against the
    device-resident shared clustering order: after the same cora_like SGD
    stream, the sharded labels and counts must equal the host
    `MultiViewEngine`'s (both are exact w.r.t. the current model, so any
    disagreement is a maintenance bug on one side)."""
    out = _sharded_multiview_vs_host((4, 2), 2048, 300)  # 4 shards of 512
    assert "OK" in out


def test_sharded_multiview_multidevice_unaligned_rows():
    """The same check with 1000 rows on a (4, 1) mesh: four shards of one
    256-row kernel tile hold 1024 rows, and the 24 padding rows (all on the
    last shard) must never show up in labels, counts or point reads."""
    out = _sharded_multiview_vs_host((4, 1), 1000, 600)
    assert "OK" in out


def test_reorganize_step_has_no_cross_row_collectives():
    """Shard-local clustering: the reorganize moves rows only within their
    shard, so its compiled program on a (4, 2) mesh holds no all-to-all,
    all-gather or collective-permute of the feature table."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.core.sharded import (make_multiview_reorganize_step,
                                        multiview_state_specs)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        k, d = 3, 64
        st = multiview_state_specs(1024, d, k, mesh)
        rep = NamedSharding(mesh, P())
        W = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=rep)
        b = jax.ShapeDtypeStruct((k,), jnp.float32, sharding=rep)
        with mesh:
            step = jax.jit(make_multiview_reorganize_step(mesh))
            txt = step.lower(st, W, b).compile().as_text()
        bad = [l for l in txt.splitlines()
               if ("all-to-all" in l or "all-gather" in l or
                   "collective-permute" in l)]
        assert not bad, bad[:3]
        print("NO_CROSS_ROW_COLLECTIVES")
    """)
    assert "NO_CROSS_ROW_COLLECTIVES" in out


_REORGANIZE_VS_NUMPY = """
    import numpy as np
    from repro.core.engine import classify
    from repro.core.sharded import PAD_GID, ShardedMultiViewHazy
    from repro.launch.mesh import make_row_mesh
    shards, n, d, k = SHARDS, 1000, 64, 5
    mesh = make_row_mesh(shards)
    sh = ShardedMultiViewHazy(mesh=mesh, n=n, d=d, k=k, M=1.0)
    n_local = sh.n_pad // shards
    assert sh.n_pad > n                  # padding rows on the last shard
    r = np.random.default_rng(5)
    # dyadic values: every margin is exact in f32 in any summation order,
    # so the device's margins equal numpy's bit for bit, ties included
    F = (r.integers(-8, 9, (n, d)) / 4).astype(np.float32)

    def reference(F, gids, W, b):
        Z = W @ F.T - b[:, None]
        Z = np.where(gids[None, :] == PAD_GID, np.inf, Z).astype(np.float32)
        order = np.concatenate([
            lo + np.argsort(np.abs(Z[:, lo:lo + n_local]).min(axis=0),
                            kind="stable")
            for lo in range(0, sh.n_pad, n_local)])
        gids_new = np.take(gids, order)
        eps_new = np.take(Z, order, axis=1)
        labels_new = np.where(gids_new[None, :] == PAD_GID, np.int8(0),
                              classify(eps_new))
        return np.take(F, order, axis=0), gids_new, eps_new, labels_new

    state = sh.put_table(F)
    F_ref = np.asarray(state.F)
    gids_ref = np.asarray(state.gids)
    for step in range(2):                # the second reorganize permutes
        W = (r.integers(-4, 5, (k, d)) / 8).astype(np.float32)
        b = (r.integers(-8, 9, k) / 2).astype(np.float32)
        F_ref, gids_ref, eps_ref, labels_ref = reference(F_ref, gids_ref,
                                                         W, b)
        state = sh._reorg(state, *sh._put_models(W, b))
        for got, want in ((state.F, F_ref), (state.gids, gids_ref),
                          (state.eps, eps_ref), (state.labels, labels_ref)):
            got = np.asarray(got)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == want.tobytes(), step
        pad = gids_ref == PAD_GID
        assert not np.array_equal(gids_ref[~pad], np.arange(n))  # moved rows
        assert pad.sum() == sh.n_pad - n
        assert pad[-(sh.n_pad - n):].all()   # padding sorts last

    hlo = sh._reorg.lower(state, *sh._put_models(W, b)).as_text()
    table = f"tensor<{n_local}x{d}xf32>"
    selects = [l for l in hlo.splitlines()   # the result's type comes last
               if "stablehlo.select" in l and l.rstrip().endswith(table)]
    assert not selects, selects[:2]
    print("REORGANIZE_MATCHES_NUMPY")
"""


@pytest.mark.parametrize("shards", [1, 4])
def test_multiview_reorganize_matches_numpy(shards):
    """The multi-view reorganize on a (shards, 1) row mesh with padding
    rows, twice in a row: the moved table, gids, margins and labels equal
    a numpy reference (a stable argsort of each shard's nearest-boundary
    distance, applied with `np.take`) bit for bit, padding rows last; and
    the lowered program moves the table's rows with the gather alone, no
    select over a table-shaped array."""
    out = _run_subprocess(_REORGANIZE_VS_NUMPY.replace("SHARDS", str(shards)),
                          devices=shards)
    assert "REORGANIZE_MATCHES_NUMPY" in out
