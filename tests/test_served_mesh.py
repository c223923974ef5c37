"""A view served on a row mesh: `engine = sharded, shards = N` cuts the
device table's rows over the first N devices. Over the wire, a four-shard
view and a one-shard view on the same table, fed the same commits, give
the same labels, COUNTs and point reads, and both equal a plain numpy
reference (an SGD replay of the acknowledged commits, margins F . W^T - b).
The per-shard window counters, the shard-wise table put and the option's
checks are tested here too. Multi-device cases run in a subprocess with
four CPU devices (`test_distributed._run_subprocess`)."""
import os

import pytest

from repro.rdbms import Catalog, PlanError
from test_distributed import _run_subprocess

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")

# a citeseer_topics table (the benchmark's generator) at a small size
_TABLE = """
        import sys
        import numpy as np
        sys.path.insert(0, BENCH)
        sys.path.insert(0, BENCH + "/data")
        import citeseer_topics, reference
        n, k = N_ROWS, 16
        table = {"hash_dim": HASH_DIM, "words_per_row": 60,
                 "topic_columns": 8, "topic_weight": 2.5, "label_noise": 0.02}
        rng = np.random.default_rng(7)
        F, truth = citeseer_topics.make(table, k, rng, n)
"""


def _run(code: str, n_rows: int, hash_dim: int = 512) -> str:
    table = (_TABLE.replace("BENCH", repr(os.path.abspath(BENCH)))
             .replace("N_ROWS", str(n_rows))
             .replace("HASH_DIM", str(hash_dim)))
    return _run_subprocess(table + code, devices=4)


_SERVED = """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.sharded import PAD_GID
        from repro.launch.mesh import make_host_mesh
        from repro.rdbms import Catalog, Executor
        from repro.rdbms.client import SqlClient
        from repro.rdbms.server import start_server_thread

        catalog = Catalog()
        catalog.register_table("papers", F, truth=truth, num_classes=k)
        ex = Executor(catalog, group_commit=16)
        server = start_server_thread(ex)
        cl = SqlClient.connect(*server.address)
        opts = "k = 16, engine = sharded, lr = 1.0, l2 = 0.0001, p = 2, q = 2"
        cl.run_one("CREATE CLASSIFICATION VIEW mesh ON papers USING MODEL "
                   f"svm WITH ({opts}, shards = 4)")
        cl.run_one("CREATE CLASSIFICATION VIEW one ON papers USING MODEL "
                   f"svm WITH ({opts})")
        views = {v: catalog.view(v).facade for v in ("mesh", "one")}
        mesh, one = views["mesh"].driver, views["one"].driver
        assert mesh.mesh.devices.shape == (4, 1) and mesh.shards == 4
        # without the option: the (1, 1) mesh of a one-chip view, though
        # four devices are visible
        assert one.mesh == make_host_mesh((1, 1)) and one.shards == 1

        groups, reorgs = [], {v: (0, 0) for v in views}   # (count, epoch)
        for e in range(1, 61):
            ids = rng.integers(0, n, 16)
            res = cl.run_one("INSERT INTO papers (id, class) VALUES " +
                             ", ".join(f"({int(i)}, {int(truth[i])})"
                                       for i in ids))
            assert res.epoch == e and res.rows == [[16, 1]], (e, res.rows)
            groups.append(ids)
            for v, f in views.items():
                if f.driver.skiing.reorgs > reorgs[v][0]:
                    reorgs[v] = (f.driver.skiing.reorgs, e)
        assert all(f.driver.kernel_rounds > 0 for f in views.values())
        assert all(r[0] > 0 for r in reorgs.values())
        # the round's models sit on every device of the view's mesh
        for d in (mesh, one):
            rep = NamedSharding(d.mesh, P())
            assert all(m.sharding == rep for m in d.models)

        W_ref, b_ref, snaps = reference.sgd_replay(
            F, truth, groups, k, 1.0, 1e-4, keep=range(len(groups) + 1))
        Z = reference.margins(F, W_ref, b_ref)            # (n, k)
        near = np.abs(Z.T) <= reference.TOL
        labels, counts, answers = {}, {}, {}
        pairs = np.stack([rng.integers(0, n, 64), rng.integers(0, k, 64)], 1)
        for v, f in views.items():
            d = f.driver
            assert reference.model_error(f.W, f.b, W_ref, b_ref) == 0.0
            gids, lab, eps = d.real_rows(f.state)
            assert np.array_equal(np.sort(gids), np.arange(n))
            full = np.empty((k, n), np.int8)
            full[:, gids] = lab
            labels[v] = full
            bad, _ = reference.label_mismatches(full, Z)
            assert bad == 0, (v, bad)
            # stored margins: under the model of the view's last reorganize
            Ws, bs = snaps[reorgs[v][1]]
            st = f.state
            assert reference.model_error(np.asarray(st.W_stored),
                                         np.asarray(st.b_stored), Ws,
                                         bs.astype(np.float32)) == 0.0
            Zs = reference.margins(F, Ws, bs)
            assert np.max(np.abs(eps - Zs[gids].T)) <= 1e-4, v
            counts[v] = [cl.run_one(f"SELECT count(*) FROM {v} WHERE "
                                    f"class = {c}").rows[0][0]
                         for c in range(k)]
            assert counts[v] == (full == 1).sum(axis=1).tolist(), v
            slack = np.abs(np.asarray(counts[v]) - (Z >= 0).sum(axis=0))
            assert np.all(slack <= near.sum(axis=1)), v
            cl.prepare(f"pt_{v}", f"SELECT label FROM {v} WHERE id = ? AND "
                                  f"view = ?")
            got = [cl.run_prepared(f"pt_{v}", [int(i), int(c)])
                   for i, c in pairs]
            assert all(r.epoch == len(groups) for r in got)
            answers[v] = np.array([r.rows[0][0] for r in got])
            want = np.where(Z[pairs[:, 0], pairs[:, 1]] >= 0, 1, -1)
            close = near[pairs[:, 1], pairs[:, 0]]
            assert np.array_equal(answers[v][~close], want[~close]), v
        assert np.array_equal(labels["mesh"], labels["one"])
        assert counts["mesh"] == counts["one"]
        assert np.array_equal(answers["mesh"], answers["one"])
        # padding rows: all on the last shard, never counted or read
        shards = sorted(views["mesh"].state.gids.addressable_shards,
                        key=lambda s: s.index[0].start)
        pads = [int((np.asarray(s.data) == PAD_GID).sum()) for s in shards]
        assert pads == [0, 0, 0, mesh.n_pad - n], pads
        flat = dict(cl.run_one("SHOW METRICS").rows)
        assert flat["view.mesh.shards"] == 4 and flat["view.one.shards"] == 1
        cl.close()
        server.stop()
        print("SERVED_MESH_OK", pads, counts["mesh"][:4])
"""


@pytest.mark.parametrize("n_rows", [2048, 1000])
def test_four_shard_view_serves_what_one_shard_and_numpy_serve(n_rows):
    """2048 rows cut into four 512-row shards; 1000 rows into four 256-row
    shards, the last one ending in 24 padding rows."""
    out = _run(_SERVED, n_rows)
    assert "SERVED_MESH_OK" in out


_COUNTERS = """
        import jax
        from repro.core.engine import covering_windows
        from repro.core.sharded import ShardedMultiViewHazy
        from repro.core.waters import holder_M
        from repro.launch.mesh import make_row_mesh
        from repro.obs import MetricsRegistry

        # the first shard's rows lie ten times nearer every boundary, so
        # its band, and the window the kernel streams there, is the widest
        F[: n // 4] *= np.float32(0.1)
        reg = MetricsRegistry()
        d = F.shape[1]
        drv = ShardedMultiViewHazy(mesh=make_row_mesh(4), n=n, d=d, k=k,
                                   M=holder_M(F, 2.0), metrics=reg)
        state = drv.init_state(F)
        n_local, bn = drv.n_pad // 4, drv.block_n

        def streamed(eps, lw, hw):
            # the kernel's tile rule (`multiview_band_reclassify`) on the host
            start, end, _ = covering_windows(eps, lw, hw)
            has = end > start
            first = np.min(np.where(has, start // bn, n_local // bn - 1))
            last = np.max(np.where(has, -(-end // bn), 0))
            return (max(last, first + 1) - first) * bn

        names = ("band.window_rows", "band.shard_window_rows_max")

        def sums(prefix=""):
            h = reg.snapshot()["histograms"]
            return [(h[prefix + m]["count"], h[prefix + m]["sum"])
                    if prefix + m in h else (0, 0.0) for m in names]

        # models that drift from the first by a little more each round: the
        # first round relabels everything, SKIING then reorganizes under
        # it, and the bands of the rounds after grow from the front of
        # each shard's shared order
        W1 = rng.normal(size=(k, d)).astype(np.float32)
        R = rng.normal(size=(k, d)).astype(np.float32)
        b = np.zeros(k)
        uneven = launches = 0
        for r in range(12):
            before, p_before = sums(), sums("profiled.")
            rounds = drv.kernel_rounds
            if r in (8, 9):
                jax.profiler.start_trace(TRACE_DIR)
            state = drv.apply_models(state, W1 + np.float32(1e-5 * r) * R, b)
            if r in (8, 9):
                jax.profiler.stop_trace()
            after, p_after = sums(), sums("profiled.")
            if drv.kernel_rounds == rounds:          # SKIING reorganized
                assert after == before and p_after == p_before
                continue
            launches += 1
            eps = np.asarray(state.eps)              # unchanged by a launch
            lw, hw = drv.lw.astype(np.float32), drv.hw.astype(np.float32)
            rows = [streamed(eps[:, s * n_local:(s + 1) * n_local], lw, hw)
                    for s in range(4)]
            want = [sum(rows), max(rows)]
            assert [a[0] - b[0] for a, b in zip(after, before)] == [1, 1]
            assert [a[1] - b[1] for a, b in zip(after, before)] == want
            got = [a[1] - b[1] for a, b in zip(p_after, p_before)]
            assert got == (want if r in (8, 9) else [0, 0]), (r, got)
            uneven += max(rows) * 4 > sum(rows)
        assert launches >= 10 and uneven >= 8, (launches, uneven)
        print("SHARD_COUNTERS_OK", launches, uneven)
"""


def test_per_shard_window_rows_sum_to_the_launch_and_give_its_widest(
        tmp_path):
    """On four shards of eight 128-row tiles each, every launch's streamed
    rows, worked out shard by shard on the host from the stored margins and
    the round's waters, sum to its `band.window_rows` observation, and
    their largest, which differs from the others' in most launches, is its
    `band.shard_window_rows_max`; the `profiled.` twins take the launches
    of a profiled window alone."""
    out = _run(_COUNTERS.replace("TRACE_DIR", repr(str(tmp_path))), 4000,
               hash_dim=4096)
    assert "SHARD_COUNTERS_OK" in out


_PUT = """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.sharded import ShardedMultiViewHazy
        from repro.launch.mesh import make_host_mesh, make_row_mesh

        for mesh in (make_row_mesh(4), make_host_mesh((1, 1))):
            d = F.shape[1]
            drv = ShardedMultiViewHazy(mesh=mesh, n=n, d=d, k=k, M=1.0)
            padded = np.concatenate([F, np.zeros((drv.n_pad - n, d),
                                                 np.float32)])
            whole = jax.device_put(padded,
                                   NamedSharding(mesh, P(("data",), None)))
            put = drv.put_table(F)
            by_start = lambda a: sorted(a.addressable_shards,
                                        key=lambda s: s.index[0].start)
            assert len(by_start(put.F)) == drv.shards
            for s_new, s_old in zip(by_start(put.F), by_start(whole)):
                assert s_new.device == s_old.device
                assert s_new.index == s_old.index
                assert np.array_equal(np.asarray(s_new.data),
                                      np.asarray(s_old.data))
            # the first reorganize then gives the same state as from the
            # padded copy
            new = drv.init_state(F)
            old = drv._reorg(put._replace(F=whole), *drv.models)
            for field in ("F", "gids", "eps", "labels"):
                assert np.array_equal(np.asarray(getattr(new, field)),
                                      np.asarray(getattr(old, field))), field
        print("PUT_OK", drv.n_pad)
"""


@pytest.mark.parametrize("n_rows", [2048, 1000])
def test_table_put_shard_by_shard_equals_the_padded_copy(n_rows):
    """`put_table` builds each shard from the host table (padding on the
    last shard alone): on four shards and on one, each shard holds the
    rows a put of the whole padded table gives it, on the same device, and
    the first reorganize gives the same rows, gids, margins and labels."""
    out = _run(_PUT, n_rows)
    assert "PUT_OK" in out


# ---------------------------------------------------------------------------
# the option's checks, in process
# ---------------------------------------------------------------------------

def _catalog():
    from repro.data import multiclass_corpus
    c = multiclass_corpus("shards", 256, 16, 4, seed=1)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.classes, num_classes=4)
    return catalog


def _too_many():
    import jax
    return {"engine": "sharded", "shards": len(jax.devices()) + 1}


@pytest.mark.parametrize("options", [
    lambda: {"engine": "sharded", "shards": 0},
    lambda: {"engine": "multiview", "shards": 2},
    _too_many,
], ids=["no-shard", "not-sharded-engine", "more-than-devices"])
def test_shards_option_is_checked_at_create(options):
    catalog = _catalog()
    with pytest.raises(PlanError, match="shards"):
        catalog.create_view("v", "t", "svm", {"k": 4, **options()})
    assert "v" not in catalog.views


def test_derived_view_takes_no_shards():
    catalog = _catalog()
    catalog.create_view("base", "t", "svm", {"k": 1})
    with pytest.raises(PlanError, match="one shard"):
        catalog.create_view("child", "base", "svm", {"shards": 2})


def test_one_shard_view_keeps_the_host_mesh():
    """Without `shards`, the sharded view's mesh is `make_host_mesh()`'s
    (1, 1) mesh and its collector reports one shard."""
    pytest.importorskip("jax")
    from repro.launch.mesh import make_host_mesh
    catalog = _catalog()
    catalog.create_view("v", "t", "svm", {"k": 4, "engine": "sharded"})
    f = catalog.view("v").facade
    assert f.driver.mesh == make_host_mesh((1, 1))
    assert f.telemetry_snapshot()["shards"] == 1
