"""Distributed features: grad compression, stragglers, multi-device subprocess
tests (sharded hazy consistency, elastic re-mesh restore)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
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
# Straggler logic (pure python)
# ---------------------------------------------------------------------------

def test_straggler_detection_and_reassignment():
    from repro.distributed import ShardAssigner, StragglerDetector
    det = StragglerDetector(n_workers=4, threshold=1.5, patience=2)
    asg = ShardAssigner(n_shards=8, n_workers=4)
    flagged = []
    for _ in range(5):
        times = {0: 1.0, 1: 1.0, 2: 1.05, 3: 3.0}  # worker 3 is slow
        flagged = det.observe(times)
    assert flagged == [3]
    newmap = asg.reassign(flagged, det)
    assert 3 not in newmap and 3 in asg.evicted
    covered = sorted(s for shards in newmap.values() for s in shards)
    assert covered == list(range(8))        # every shard still owned
    assert asg.owner_of(3) != 3


def test_straggler_no_false_positive():
    from repro.distributed import StragglerDetector
    det = StragglerDetector(n_workers=4, threshold=1.5, patience=3)
    for _ in range(10):
        assert det.observe({w: 1.0 + 0.05 * w for w in range(4)}) == []


# ---------------------------------------------------------------------------
# Compression (multi-device, subprocess)
# ---------------------------------------------------------------------------

def test_compressed_allreduce_accuracy():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed import (make_compressed_grad_allreduce,
                                       error_feedback_init)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("pod",))
        allred = make_compressed_grad_allreduce("pod", 8)
        r = np.random.default_rng(0)
        g_all = jnp.asarray(r.normal(size=(8, 64)), jnp.float32)
        err0 = {"g": jnp.zeros((8, 64), jnp.float32)}

        def f(g, err):
            out, err2 = allred({"g": g}, err)
            return out["g"], err2["g"]

        fn = jax.jit(jax.shard_map(f, mesh=mesh,
                                   in_specs=(P("pod"), P("pod")),
                                   out_specs=(P("pod"), P("pod"))))
        # accumulate over rounds: error feedback must keep the running mean
        # close to the true mean
        total_hat = np.zeros(64); total_true = np.zeros(64)
        err = err0["g"]
        for step in range(20):
            g_step = g_all * (1.0 + 0.1 * step)
            mean_hat, err = fn(g_step, err)
            total_hat += np.asarray(mean_hat)[0]
            total_true += np.asarray(jnp.mean(g_step, axis=0))
        rel = np.abs(total_hat - total_true).max() / (np.abs(total_true).max() + 1e-9)
        print("REL", rel)
        assert rel < 0.02, rel
    """)
    assert "REL" in out


# ---------------------------------------------------------------------------
# Sharded hazy engine on a real (fake-device) mesh
# ---------------------------------------------------------------------------

def test_sharded_hazy_multidevice_consistency():
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.sharded import ShardedHazy
        from repro.core import zero_model, sgd_step
        from repro.data import forest_like, example_stream
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        corpus = forest_like(scale=0.01)
        n = (corpus.features.shape[0] // 8) * 8
        F = np.ascontiguousarray(corpus.features[:n, :52])  # 52 % 2 == 0
        sh = ShardedHazy(mesh=mesh, n=n, d=52, M=1.0, p=2.0, cap_frac=1/4)
        state = sh.init_state(F)
        model = zero_model(52)
        stream = example_stream(corpus, seed=3, label_noise=0.0)
        for _, f, y in [next(stream) for _ in range(400)]:
            model = sgd_step(model, f[:52], y, lr=0.02, l2=1e-3)
            state = sh.apply_model(state, jnp.asarray(model.w),
                                   jnp.asarray(model.b, jnp.float32))
        truth = np.where(F @ model.w - model.b >= 0, 1, -1)
        # per-shard permutations: compare via perm indices
        perm = np.asarray(state.perm)
        labels = np.asarray(state.labels)
        assert np.array_equal(truth[perm], labels)
        assert sh.all_members(state) == int((truth == 1).sum())
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
    """DESIGN.md claim: shard-local clustering -> reorganization needs no
    collectives beyond the model-axis eps psum (no all-to-all / all-gather
    of the feature table)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.core.sharded import make_reorganize_step, state_specs
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        st = state_specs(1024, 64, mesh)
        w = jax.ShapeDtypeStruct((64,), jnp.float32,
                                 sharding=NamedSharding(mesh, P("model")))
        b = jax.ShapeDtypeStruct((), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
        with mesh:
            txt = jax.jit(make_reorganize_step(mesh)).lower(st, w, b)\
                     .compile().as_text()
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


# ---------------------------------------------------------------------------
# Elastic scaling: checkpoint on one mesh, restore on a smaller one
# ---------------------------------------------------------------------------

def test_elastic_remesh_restore(tmp_path):
    tmp_path = str(tmp_path)
    out = _run_subprocess(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models import build
        from repro.models.steps import (init_train_state, make_train_step,
                                        train_state_specs)
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_elastic_mesh
        from repro.data import TokenStream

        cfg = smoke_config("tinyllama-1.1b")
        mdl = build(cfg)
        ds = TokenStream(vocab_size=cfg.vocab_size, batch=4, seq_len=16, seed=0)
        step_fn = jax.jit(make_train_step(mdl))

        def batches(i):
            return {{k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}}

        # train 3 steps on an 8-device mesh
        mesh8 = make_elastic_mesh(8, model_parallel=2)
        with mesh8:
            state = init_train_state(mdl)
            for i in range(3):
                state, _ = step_fn(state, batches(i))
        save_checkpoint({tmp_path!r}, state, 3)

        # "lose" 4 devices: restore onto a 4-device mesh and keep training
        mesh4 = make_elastic_mesh(4, model_parallel=2)
        from repro.models.steps import train_state_specs
        abstract = train_state_specs(mdl, mesh4)
        with mesh4:
            restored, step = restore_checkpoint({tmp_path!r}, abstract)
            assert step == 3
            restored, m = step_fn(restored, batches(3))
        assert np.isfinite(float(m["loss"]))
        print("ELASTIC_OK", float(m["loss"]))
    """)
    assert "ELASTIC_OK" in out
