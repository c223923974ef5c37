"""Relational front-end: parser, planner tiers, WAL replay, and the
SQL-vs-direct equivalence contract.

The core property (ISSUE 4): ANY DML statement stream replayed through the
SQL executor must yield labels, counts, and waters IDENTICAL to direct
engine calls on the same stream — the front-end adds routing, batching and
bookkeeping, never different maintenance. Checked for all three engines
behind the catalog (single-view HazyEngine, k = 16 MultiViewEngine, and
ShardedMultiViewHazy) under eager, lazy, and hybrid policies (sharded is
eager-only by construction).

Everything runs with cost_mode=modeled so SKIING's reorganization schedule
is deterministic (S cancels out of charge vs threshold).
"""
import numpy as np
import pytest

from repro.core import ClassificationView, MulticlassView
from repro.data import multiclass_corpus, synthetic_corpus
from repro.rdbms import (Catalog, Executor, ParseError, PlanError, UpdateLog,
                         parse)
from repro.rdbms import ast_nodes as A


# ---------------------------------------------------------------------------
# Parser
# ---------------------------------------------------------------------------

def test_parse_create_statements():
    ct, cv = parse("""
        CREATE TABLE papers FROM CORPUS cora_like WITH (scale = 0.1);
        CREATE CLASSIFICATION VIEW v ON papers USING MODEL svm
            WITH (policy = hybrid, k = 16, buffer_frac = 0.05, p = inf);
    """)
    assert ct == A.CreateTable("papers", "cora_like", {"scale": 0.1})
    assert cv.name == "v" and cv.table == "papers" and cv.model == "svm"
    assert cv.options == {"policy": "hybrid", "k": 16, "buffer_frac": 0.05,
                          "p": float("inf")}
    assert isinstance(cv.options["k"], int)


def test_parse_dml_and_select():
    ins, upd, um, dele, sel, cnt, topk, ex = parse("""
        INSERT INTO t (id, label) VALUES (3, 1), (4, -1);
        UPDATE t SET label = -1 WHERE id = 5;
        UPDATE MODEL ON v;
        DELETE FROM t WHERE id = 9;
        SELECT id, view, label FROM v WHERE id IN (1, 2) AND view = 3;
        SELECT COUNT(*) FROM v WHERE label = 1;
        SELECT id, margin FROM v ORDER BY margin DESC LIMIT 7;
        EXPLAIN SELECT label FROM v WHERE id = 0;
    """)
    assert ins == A.Insert("t", [(3, 1.0), (4, -1.0)])
    assert upd == A.Update("t", 5, -1.0)
    assert um == A.UpdateModel("v")
    assert dele == A.Delete("t", 9)
    assert sel.where.ids == [1, 2] and sel.where.view == 3
    assert cnt.count and cnt.where.label == 1
    assert topk.order_by == "margin" and topk.descending and topk.limit == 7
    assert isinstance(ex, A.Explain) and isinstance(ex.stmt, A.Select)


@pytest.mark.parametrize("bad", [
    "SELECT bogus FROM v",
    "SELECT label FROM v WHERE label = 2",
    "SELECT id FROM v ORDER BY id",
    "UPDATE t SET margin = 1 WHERE id = 0",
    "INSERT INTO t (label, id) VALUES (1, 1)",
    "CREATE VIEW v ON t USING MODEL svm",
    "SELECT label FROM",
])
def test_parse_errors(bad):
    with pytest.raises(ParseError):
        parse(bad)


# ---------------------------------------------------------------------------
# Shared equivalence harness
# ---------------------------------------------------------------------------

GROUP = 8          # WAL group-commit size used throughout


class DirectMirror:
    """Replays the SAME statement stream via direct engine calls, including
    the WAL's group-commit semantics (flush at GROUP records, flush before
    reads / UPDATE MODEL, delete splits the batch)."""

    def __init__(self, insert_batch, delete_fn=None, read_flushes=True):
        self.pending = []
        self.insert_batch = insert_batch        # f(ids, labels)
        self.delete_fn = delete_fn
        self.read_flushes = read_flushes

    def dml(self, entity_id, label, op="insert"):
        self.pending.append((op, entity_id, label))
        if len(self.pending) >= GROUP:
            self.flush()

    def flush(self):
        batch = []
        for op, i, y in self.pending:
            if op == "delete":
                if batch:
                    self.insert_batch([b[0] for b in batch],
                                      [b[1] for b in batch])
                    batch = []
                self.delete_fn(i)
            else:
                batch.append((i, y))
        if batch:
            self.insert_batch([b[0] for b in batch], [b[1] for b in batch])
        self.pending = []


def _single_view_setup(policy):
    c = synthetic_corpus("eqv", 400, 24, seed=2)
    kw = dict(method="svm", policy=policy, norm=(2.0, 2.0), lr=0.1, l2=1e-4,
              alpha=1.0, buffer_frac=0.02 if policy == "hybrid" else 0.0,
              cost_mode="modeled")
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": policy, "p": 2, "q": 2,
                         "buffer_frac": kw["buffer_frac"],
                         "cost_mode": "modeled"})
    direct = ClassificationView(c.features, **kw)
    return c, catalog, direct


@pytest.mark.parametrize("policy", ["eager", "lazy", "hybrid"])
def test_sql_equals_direct_single_view(policy):
    c, catalog, direct = _single_view_setup(policy)
    ex = Executor(catalog, group_commit=GROUP)
    mirror = DirectMirror(
        lambda ids, ys: direct.insert_examples(ids, ys, batched=True),
        delete_fn=None)
    facade = catalog.view("v").facade
    n = c.features.shape[0]
    rng = np.random.default_rng(31)

    for step in range(240):
        u = rng.random()
        if u < 0.60:                                       # INSERT batch
            m = int(rng.integers(1, 5))
            rows, stmts = [], []
            for _ in range(m):
                i = int(rng.integers(0, n))
                y = int(c.labels[i])
                stmts.append(f"({i}, {y})")
                rows.append((i, y))
            ex.execute_one(f"INSERT INTO t (id, label) VALUES "
                           f"{', '.join(stmts)}")
            for i, y in rows:
                mirror.dml(i, float(y))
        elif u < 0.72:                                     # UPDATE = example
            i = int(rng.integers(0, n))
            y = -int(c.labels[i])
            ex.execute_one(f"UPDATE t SET label = {y} WHERE id = {i}")
            mirror.dml(i, float(y), op="update")
        elif u < 0.88:                                     # point SELECT
            i = int(rng.integers(0, n))
            got = ex.execute_one(
                f"SELECT label FROM v WHERE id = {i}").rows[0][0]
            mirror.flush()
            if policy == "hybrid":
                want, _ = direct.engine.hybrid_label(i)
            else:
                want = direct.engine.label(i)
            assert got == want, (step, i)
        elif u < 0.95:                                     # COUNT
            got = ex.execute_one(
                "SELECT count(*) FROM v WHERE label = 1").rows[0][0]
            mirror.flush()
            assert got == direct.engine.all_members(), step
        else:                                              # UPDATE MODEL
            ex.execute_one("UPDATE MODEL ON v")
            mirror.flush()
            direct.engine.apply_model(direct.model)

    ex.execute_one("COMMIT")
    mirror.flush()
    se, de = facade.view.engine, direct.engine
    assert se.all_members() == de.all_members()
    assert np.array_equal(se.labels_sorted, de.labels_sorted)
    assert np.array_equal(se.perm, de.perm)
    assert np.allclose(se.eps_sorted, de.eps_sorted)
    assert se.waters.lw == de.waters.lw and se.waters.hw == de.waters.hw
    assert se.skiing.reorgs == de.skiing.reorgs
    assert (se._pending is None) == (de._pending is None)
    assert se.check_consistent() and de.check_consistent()


def test_sql_equals_direct_single_view_with_delete():
    """DELETE retrains from scratch (footnote 2) — order-preserving around
    the group commit — and must match the same direct calls."""
    c, catalog, direct = _single_view_setup("eager")
    ex = Executor(catalog, group_commit=GROUP)
    direct_log = []

    def direct_insert(ids, ys):
        direct_log.extend(zip(ids, ys))
        direct.insert_examples(ids, ys, batched=True)

    def direct_delete(eid):
        keep = [(i, y) for i, y in direct_log if i != eid]
        direct_log[:] = keep
        direct.examples = [(direct.F[i], y) for i, y in keep]
        direct.retrain_from_scratch()

    mirror = DirectMirror(direct_insert, delete_fn=direct_delete)
    n = c.features.shape[0]
    rng = np.random.default_rng(5)
    for _ in range(60):
        i = int(rng.integers(0, n))
        y = int(c.labels[i])
        ex.execute_one(f"INSERT INTO t (id, label) VALUES ({i}, {y})")
        mirror.dml(i, float(y))
        if rng.random() < 0.1:
            j = int(rng.integers(0, n))
            ex.execute_one(f"DELETE FROM t WHERE id = {j}")
            mirror.dml(j, 0.0, op="delete")
    ex.execute_one("COMMIT")
    mirror.flush()
    se, de = catalog.view("v").facade.view.engine, direct.engine
    assert np.array_equal(se.labels_sorted, de.labels_sorted)
    assert se.all_members() == de.all_members()
    assert se.waters.lw == de.waters.lw and se.waters.hw == de.waters.hw


K = 16             # the issue's multiclass width


@pytest.mark.parametrize("policy", ["eager", "lazy", "hybrid"])
def test_sql_equals_direct_multiclass_k16(policy):
    c = multiclass_corpus("eqk", 360, 24, K, seed=4)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.classes, num_classes=K)
    catalog.create_view("v", "t", "svm",
                        {"policy": policy, "k": K, "p": 2, "q": 2,
                         "cost_mode": "modeled"})
    buffer_frac = 0.01 if policy == "hybrid" else 0.0
    direct = MulticlassView(c.features, K, policy=policy, lr=0.1, l2=1e-4,
                            alpha=1.0, p=2.0, q=2.0, cost_mode="modeled",
                            buffer_frac=buffer_frac, vectorized=True)
    ex = Executor(catalog, group_commit=GROUP)
    mirror = DirectMirror(
        lambda ids, ys: direct.insert_examples(
            [int(i) for i in ids], [int(y) for y in ys]))
    facade = catalog.view("v").facade
    n = c.features.shape[0]
    rng = np.random.default_rng(77)

    for step in range(160):
        u = rng.random()
        if u < 0.62:                                       # INSERT batch
            m = int(rng.integers(1, 5))
            rows = [(int(rng.integers(0, n)),) for _ in range(m)]
            rows = [(i, int(c.classes[i])) for (i,) in rows]
            ex.execute_one(
                "INSERT INTO t (id, class) VALUES "
                + ", ".join(f"({i}, {cl})" for i, cl in rows))
            for i, cl in rows:
                mirror.dml(i, cl)
        elif u < 0.78:                                     # one-view point
            i = int(rng.integers(0, n))
            v = int(rng.integers(0, K))
            got = ex.execute_one(
                f"SELECT label FROM v WHERE id = {i} AND view = {v}"
            ).rows[0][0]
            mirror.flush()
            if policy == "hybrid":
                want, _ = direct.engine.hybrid_label(v, i)
            else:
                want = direct.engine.label(v, i)
            assert got == want, (step, i, v)
        elif u < 0.88:                                     # all-views point
            i = int(rng.integers(0, n))
            got = [r[2] for r in ex.execute_one(
                f"SELECT id, view, label FROM v WHERE id = {i}").rows]
            mirror.flush()
            if policy == "hybrid":
                want = direct.engine.hybrid_labels_of(i)[0]
            else:
                want = direct.engine.labels_of(i)
            assert np.array_equal(got, want), (step, i)
        elif u < 0.95:                                     # COUNT one class
            v = int(rng.integers(0, K))
            got = ex.execute_one(
                f"SELECT count(*) FROM v WHERE class = {v}").rows[0][0]
            mirror.flush()
            assert got == direct.engine.all_members()[v], step
        else:                                              # UPDATE MODEL
            ex.execute_one("UPDATE MODEL ON v")
            mirror.flush()
            direct.engine.apply_models(direct.W, direct.b)

    ex.execute_one("COMMIT")
    mirror.flush()
    se, de = facade.mc.engine, direct.engine
    assert np.array_equal(se.all_members(), de.all_members())
    assert np.array_equal(se.labels_sorted, de.labels_sorted)
    assert np.array_equal(se.perm, de.perm)
    assert np.array_equal(se.lw, de.lw) and np.array_equal(se.hw, de.hw)
    assert np.array_equal(se.pending, de.pending)
    assert np.array_equal(se.reorg_counts, de.reorg_counts)
    assert se.check_consistent() and de.check_consistent()


def test_sql_equals_direct_sharded():
    """Third engine behind the catalog: `ShardedMultiViewHazy` on a (1, 1)
    host mesh (interpret-mode Pallas kernel). The SQL path's stacked SGD +
    kernel rounds must match a hand-driven sharded twin exactly."""
    jax = pytest.importorskip("jax")
    if jax.default_backend() not in ("cpu", "tpu"):
        pytest.skip("needs cpu or tpu")
    from repro.core.sharded import ShardedMultiViewHazy
    from repro.core.waters import holder_M
    from repro.launch.mesh import make_host_mesh

    k, n, d = 4, 256, 16
    c = multiclass_corpus("eqs", n, d, k, seed=9)
    F = np.ascontiguousarray(c.features, np.float32)
    catalog = Catalog()
    catalog.register_table("t", F, truth=c.classes, num_classes=k)
    catalog.create_view("v", "t", "svm",
                        {"engine": "sharded", "k": k, "p": 2, "q": 2,
                         "cap_frac": 0.5})
    facade = catalog.view("v").facade
    ex = Executor(catalog, group_commit=GROUP)

    driver = ShardedMultiViewHazy(mesh=make_host_mesh((1, 1)), n=n, d=d, k=k,
                                  M=holder_M(F, 2.0), p=2.0)
    state = driver.init_state(F)
    W = np.zeros((k, d), np.float32)
    b = np.zeros(k, np.float64)
    lr, l2 = 0.1, 1e-4
    pending = []

    def flush():
        nonlocal state, W, b
        if not pending:
            return
        for i, cls in pending:
            f = F[i]
            y = np.where(np.arange(k) == cls, 1.0, -1.0)
            z = W @ f - b.astype(np.float32)
            g = np.where(y * z.astype(np.float64) < 1.0, -y, 0.0)
            W = W * (1.0 - lr * l2)
            W -= (lr * g).astype(np.float32)[:, None] * f[None, :]
            b = b - lr * (-g)
        state = driver.apply_models(state, W, b)
        pending.clear()

    rng = np.random.default_rng(123)
    for _ in range(10):
        rows = [(int(rng.integers(0, n)),) for _ in range(GROUP)]
        rows = [(i, int(c.classes[i])) for (i,) in rows]
        ex.execute_one("INSERT INTO t (id, class) VALUES "
                       + ", ".join(f"({i}, {cl})" for i, cl in rows))
        for i, cl in rows:
            pending.append((i, cl))
            if len(pending) >= GROUP:
                flush()
        # point read through SQL vs the direct probe+margin pair
        i = int(rng.integers(0, n))
        got = [r[2] for r in ex.execute_one(
            f"SELECT id, view, label FROM v WHERE id = {i}").rows]
        flush()
        want, _ = driver.hybrid_labels_of(state, i)
        assert np.array_equal(got, want), i

    ex.execute_one("COMMIT")
    flush()
    assert np.array_equal(facade.counts(), driver.all_members(state))
    assert np.array_equal(np.asarray(facade.state.labels),
                          np.asarray(state.labels))
    assert np.array_equal(np.asarray(facade.state.gids),
                          np.asarray(state.gids))
    assert np.array_equal(facade.driver.lw, driver.lw)
    assert np.array_equal(facade.driver.hw, driver.hw)
    assert facade.driver.skiing.reorgs == driver.skiing.reorgs


@pytest.mark.parametrize("n", [1000, 1157])
def test_sql_sharded_unaligned_table_matches_multiview(n):
    """engine=sharded on a table whose row count is no multiple of the
    kernel's 128-row tile, so the device table carries padding rows. Through
    SQL, its labels, counts, members and point reads must equal a host
    `MultiViewEngine` view on the same table and stream, and no padding row
    may ever be counted or returned."""
    pytest.importorskip("jax")
    from repro.core.sharded import PAD_GID

    k, d = 4, 16
    c = multiclass_corpus("pad", n, d, k, seed=3)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.classes, num_classes=k)
    opts = {"k": k, "p": 2, "q": 2, "lr": 0.5}
    catalog.create_view("dev", "t", "svm", {**opts, "engine": "sharded"})
    catalog.create_view("host", "t", "svm", {**opts, "engine": "multiview"})
    driver = catalog.view("dev").facade.driver
    assert driver.n_pad > n and driver.n_pad % driver.block_n == 0
    ex = Executor(catalog, group_commit=GROUP)

    rng = np.random.default_rng(4)
    for _ in range(8):
        ids = rng.integers(0, n, GROUP)
        ex.execute_one("INSERT INTO t (id, class) VALUES " + ", ".join(
            f"({int(i)}, {int(c.classes[i])})" for i in ids))
    ex.execute_one("COMMIT")
    assert driver.kernel_rounds > driver.overflows    # the kernel relabeled

    def q(sql):
        return ex.execute_one(sql).rows

    for v in range(k):
        counts = [q(f"SELECT count(*) FROM {view} WHERE class = {v}")[0][0]
                  for view in ("dev", "host")]
        assert counts[0] == counts[1], (v, counts)
        sides = {}
        for lab in (1, -1):
            got = [sorted(r[0] for r in q(f"SELECT id FROM {view} WHERE "
                                          f"class = {v} AND label = {lab}"))
                   for view in ("dev", "host")]
            assert got[0] == got[1], (v, lab)
            sides[lab] = got[0]
        # the two sides list every real entity once and nothing else
        assert sorted(sides[1] + sides[-1]) == list(range(n))
        assert len(sides[1]) == counts[0]
    assert 0 < sum(len(q(f"SELECT id FROM dev WHERE class = {v} AND "
                         f"label = 1")) for v in range(k)) < n * k
    for i in [0, n // 2, n - 1, *rng.integers(0, n, 8)]:
        got = [q(f"SELECT id, view, label FROM {view} WHERE id = {int(i)}")
               for view in ("dev", "host")]
        assert got[0] == got[1], i

    gids, labels, eps = driver.real_rows(catalog.view("dev").facade.state)
    assert sorted(gids) == list(range(n)) and PAD_GID not in gids
    assert set(np.unique(labels)) <= {-1, 1}
    state = catalog.view("dev").facade.state
    pad = np.asarray(state.gids) == PAD_GID
    assert pad.sum() == driver.n_pad - n
    assert not np.asarray(state.labels)[:, pad].any()       # label 0


# ---------------------------------------------------------------------------
# Hybrid point SELECTs: tier counters (acceptance criterion)
# ---------------------------------------------------------------------------

def test_hybrid_point_selects_touch_F_only_on_probe_miss():
    c = synthetic_corpus("tier", 500, 24, seed=6)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": "hybrid", "p": 2, "q": 2,
                         "buffer_frac": 0.02, "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=GROUP)
    facade = catalog.view("v").facade
    n = c.features.shape[0]
    rng = np.random.default_rng(8)
    for _ in range(12):
        rows = [(int(rng.integers(0, n)),) for _ in range(GROUP)]
        ex.execute_one("INSERT INTO t (id, label) VALUES " + ", ".join(
            f"({i}, {int(c.labels[i])})" for (i,) in rows))

    before = dict(facade.tier_hits)
    disk_before = facade.disk_touches
    reads = 200
    for _ in range(reads):
        i = int(rng.integers(0, n))
        ex.execute_one(f"SELECT label FROM v WHERE id = {i}")
    hits = {t: facade.tier_hits[t] - before[t] for t in facade.tier_hits}
    # every read resolved by the §3.5.2 tier chain, none by plain map reads
    assert hits["map"] == 0
    assert hits["water"] + hits["buffer"] + hits["disk"] == reads
    # THE acceptance check: the feature table was touched exactly once per
    # probe miss ("disk" tier) and never otherwise
    assert facade.disk_touches - disk_before == hits["disk"]
    assert hits["water"] > 0          # the waters tier did real work
    # labels stay exact w.r.t. the current model
    m = facade.view.model
    truth = np.where(c.features @ m.w - m.b >= 0, 1, -1)
    for i in range(0, n, 17):
        got = ex.execute_one(
            f"SELECT label FROM v WHERE id = {i}").rows[0][0]
        assert got == truth[i]


def test_explain_point_select_reports_actual_tier():
    c = synthetic_corpus("expl", 400, 16, seed=12)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": "hybrid", "p": 2, "q": 2,
                         "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=4)
    rng = np.random.default_rng(3)
    n = c.features.shape[0]
    for _ in range(10):
        i = int(rng.integers(0, n))
        ex.execute_one(
            f"INSERT INTO t (id, label) VALUES ({i}, {int(c.labels[i])})")
    before = dict(catalog.view("v").facade.tier_hits)
    res = ex.execute_one("EXPLAIN SELECT label FROM v WHERE id = 7")
    assert res.columns[0] == "step"
    kinds = [r[0] for r in res.rows]
    assert kinds == ["point", "probe(actual)"]
    est_row, actual_row = res.rows
    assert est_row[1].startswith("probe(")        # planned tier chain
    assert actual_row[1] in ("water", "buffer", "disk")
    # the dry-run probe is tier-counted like any §3.5.2 probe
    after = catalog.view("v").facade.tier_hits
    assert sum(after.values()) == sum(before.values()) + 1
    assert after[actual_row[1]] == before[actual_row[1]] + 1
    # non-point EXPLAINs price the band partition
    res = ex.execute_one("EXPLAIN SELECT id FROM v WHERE label = 1")
    assert res.rows[0][0] == "scan"
    assert res.rows[0][1] == "band-partition"
    assert res.rows[0][2] >= 0


# ---------------------------------------------------------------------------
# Scans, top-k, WAL replay
# ---------------------------------------------------------------------------

def _warm_executor(policy="hybrid", seed=21):
    c = synthetic_corpus("scan", 400, 16, seed=seed)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": policy, "p": 2, "q": 2,
                         "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=GROUP)
    rng = np.random.default_rng(seed)
    n = c.features.shape[0]
    for _ in range(8):
        rows = [int(rng.integers(0, n)) for _ in range(GROUP)]
        ex.execute_one("INSERT INTO t (id, label) VALUES " + ", ".join(
            f"({i}, {int(c.labels[i])})" for i in rows))
    ex.execute_one("COMMIT")
    return c, catalog, ex


def test_band_scan_matches_members_and_count():
    c, catalog, ex = _warm_executor()
    eng = catalog.view("v").facade.view.engine
    got = sorted(r[0] for r in ex.execute_one(
        "SELECT id FROM v WHERE label = 1"))
    assert got == sorted(int(x) for x in eng.members())
    cnt = ex.execute_one("SELECT count(*) FROM v WHERE label = 1").rows[0][0]
    assert cnt == len(got) == eng.all_members()
    neg = ex.execute_one("SELECT count(*) FROM v WHERE label = -1").rows[0][0]
    assert cnt + neg == c.features.shape[0]


def test_topk_margin_matches_bruteforce():
    c, catalog, ex = _warm_executor()
    facade = catalog.view("v").facade
    m = facade.view.model
    z = np.asarray(c.features @ m.w - m.b, np.float64)
    for desc in (True, False):
        order = "DESC" if desc else "ASC"
        rows = ex.execute_one(
            f"SELECT id, margin FROM v ORDER BY margin {order} LIMIT 9").rows
        got = np.array([r[1] for r in rows])
        want = np.sort(z)[::-1][:9] if desc else np.sort(z)[:9]
        assert np.allclose(got, want), order
    # the plan prices candidates, not the full table
    res = ex.execute_one(
        "EXPLAIN SELECT id, margin FROM v ORDER BY margin DESC LIMIT 9")
    assert res.rows[0][0] == "topk"
    assert res.rows[0][2] <= c.features.shape[0]


def test_topk_margin_exact_under_pending_lazy_model():
    """ORDER BY margin must widen the Eq. 2 candidate slack by the PENDING
    model's drift: a lazy flush right before the read leaves the engine
    waters stale, and the stale slack can exclude true top-k rows."""
    c = synthetic_corpus("lzk", 400, 16, seed=25)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": "lazy", "p": 2, "q": 2,
                         "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=64)   # stays pending until the read
    rng = np.random.default_rng(26)
    n = c.features.shape[0]
    facade = catalog.view("v").facade
    touched_beyond_limit = False
    for _ in range(4):
        rows = [int(rng.integers(0, n)) for _ in range(20)]
        ex.execute_one("INSERT INTO t (id, label) VALUES " + ", ".join(
            f"({i}, {int(c.labels[i])})" for i in rows))
        ex.execute_one("COMMIT")
        # pin a freshly clustered state: waters (0, 0), stored eps = this
        # model's margins — any later drift exists ONLY in the pending model
        facade.view.engine.reorganize()
        rows = [int(rng.integers(0, n)) for _ in range(20)]
        ex.execute_one("INSERT INTO t (id, label) VALUES " + ", ".join(
            f"({i}, {-int(c.labels[i])})" for i in rows))
        # the SELECT flushes the queued group -> apply_model defers with
        # engine waters NOT updated, then top-k runs against the pending
        # model: only the prospective Eq. 2 slack keeps it exact
        got = [r[1] for r in ex.execute_one(
            "SELECT id, margin FROM v ORDER BY margin DESC LIMIT 6").rows]
        _, _, touched = facade.top_margins(0, 6, True)
        touched_beyond_limit |= touched > 6
        m = facade.view.model
        z = np.asarray(c.features @ m.w - m.b, np.float64)
        assert np.allclose(got, np.sort(z)[::-1][:6])
    assert touched_beyond_limit     # the pending drift really widened slack


def test_delete_rejected_before_wal_on_multiview():
    """DELETE on a table whose view cannot retrain must fail BEFORE the
    record enters the WAL — queued DML survives and later commits."""
    k = 4
    mc = multiclass_corpus("del", 300, 16, k, seed=27)
    catalog = Catalog()
    catalog.register_table("t", mc.features, truth=mc.classes, num_classes=k)
    catalog.create_view("v", "t", "svm", {"k": k, "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=64)
    ex.execute_one("INSERT INTO t (id, class) VALUES (1, 2), (3, 0)")
    with pytest.raises(PlanError):
        ex.execute_one("DELETE FROM t WHERE id = 1")
    with pytest.raises(PlanError):               # EXPLAIN surfaces it too
        ex.execute_one("EXPLAIN DELETE FROM t WHERE id = 1")
    # nothing was lost: both queued inserts commit as one round
    assert len(ex.log.pending["t"]) == 2
    ex.execute_one("COMMIT")
    assert catalog.view("v").facade.engine.stats.rounds == 1


def test_point_select_conjoined_label_predicate_filters():
    c, catalog, ex = _warm_executor(seed=28)
    eng = catalog.view("v").facade.view.engine
    pos = int(eng.members()[0])
    hit = ex.execute_one(
        f"SELECT id, label FROM v WHERE id = {pos} AND label = 1").rows
    miss = ex.execute_one(
        f"SELECT id, label FROM v WHERE id = {pos} AND label = -1").rows
    assert hit == [(pos, 1)] and miss == []


def test_bare_count_star_is_table_cardinality():
    c, catalog, ex = _warm_executor(seed=30)
    n = c.features.shape[0]
    assert ex.execute_one("SELECT count(*) FROM v").rows == [(n,)]
    res = ex.execute_one("EXPLAIN SELECT count(*) FROM v")
    assert res.rows[0][1] == "table-cardinality"
    pos = ex.execute_one("SELECT count(*) FROM v WHERE label = 1").rows[0][0]
    assert 0 < pos < n


def test_class_scan_honors_conjoined_label_polarity():
    """class = c selects the one-vs-all view; a conjoined label = -1 must
    return that view's NON-members (and agree with the count branch)."""
    k = 3
    mc = multiclass_corpus("pol", 240, 16, k, seed=35)
    catalog = Catalog()
    catalog.register_table("t", mc.features, truth=mc.classes, num_classes=k)
    catalog.create_view("v", "t", "svm", {"k": k, "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=8)
    rng = np.random.default_rng(36)
    for _ in range(6):
        rows = [int(rng.integers(0, 240)) for _ in range(8)]
        ex.execute_one("INSERT INTO t (id, class) VALUES " + ", ".join(
            f"({i}, {int(mc.classes[i])})" for i in rows))
    pos = ex.execute_one("SELECT id FROM v WHERE class = 1").rows
    neg = ex.execute_one("SELECT id FROM v WHERE class = 1 AND label = -1").rows
    assert len(pos) + len(neg) == 240
    assert not (set(r[0] for r in pos) & set(r[0] for r in neg))
    cnt_neg = ex.execute_one(
        "SELECT count(*) FROM v WHERE class = 1 AND label = -1").rows[0][0]
    assert cnt_neg == len(neg)


def test_logistic_rejected_on_multiview_engines():
    mc = multiclass_corpus("logi", 240, 16, 3, seed=37)
    catalog = Catalog()
    catalog.register_table("t", mc.features, truth=mc.classes, num_classes=3)
    with pytest.raises(PlanError):       # would silently train hinge SVM
        catalog.create_view("v", "t", "logistic", {"k": 3})
    c2 = synthetic_corpus("logi1", 240, 16, seed=38)
    catalog.register_table("b", c2.features, truth=c2.labels)
    catalog.create_view("w", "b", "logistic", {})    # k = 1 hazy: fine
    assert catalog.view("w").facade.view.method == "logistic"


def test_point_select_limit_caps_probes():
    c, catalog, ex = _warm_executor(seed=33)
    facade = catalog.view("v").facade
    before = sum(facade.tier_hits.values())
    ids = ", ".join(str(i) for i in range(40))
    res = ex.execute_one(
        f"SELECT id, label FROM v WHERE id IN ({ids}) LIMIT 3")
    assert len(res.rows) == 3
    assert len(res.tiers_used) == 3          # probed 3 ids, not 40
    assert sum(facade.tier_hits.values()) - before == 3


def test_wal_replay_reproduces_engine_state(tmp_path):
    wal_file = str(tmp_path / "log.jsonl")
    c = synthetic_corpus("replay", 300, 16, seed=14)

    def fresh_catalog():
        cat = Catalog()
        cat.register_table("t", c.features, truth=c.labels)
        cat.create_view("v", "t", "svm",
                        {"policy": "lazy", "p": 2, "q": 2,
                         "cost_mode": "modeled"})
        return cat

    catalog = fresh_catalog()
    ex = Executor(catalog, group_commit=5, wal_path=wal_file)
    rng = np.random.default_rng(15)
    n = c.features.shape[0]
    for _ in range(37):
        i = int(rng.integers(0, n))
        ex.execute_one(
            f"INSERT INTO t (id, label) VALUES ({i}, {int(c.labels[i])})")
    ex.execute_one("COMMIT")
    ex.log.close()

    # recovery: load the JSONL history, replay into a fresh catalog — commit
    # boundaries come from the markers, so the engine trajectory is identical
    history = UpdateLog.load(wal_file)
    assert any(r.op == "commit" for r in history)
    catalog2 = fresh_catalog()
    UpdateLog.replay_into(history, catalog2)
    e1 = catalog.view("v").facade.view.engine
    e2 = catalog2.view("v").facade.view.engine
    assert e1.all_members() == e2.all_members()
    assert np.array_equal(e1.labels_sorted, e2.labels_sorted)
    assert e1.waters.lw == e2.waters.lw and e1.waters.hw == e2.waters.hw
    assert e1.skiing.reorgs == e2.skiing.reorgs


def test_group_commit_amortizes_rounds():
    """G inserts -> ONE engine round per commit, not G rounds."""
    c = synthetic_corpus("amort", 300, 16, seed=18)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": "eager", "p": 2, "q": 2,
                         "cost_mode": "modeled"})
    ex = Executor(catalog, group_commit=16)
    eng = catalog.view("v").facade.view.engine
    for j in range(32):
        ex.execute_one(f"INSERT INTO t (id, label) VALUES "
                       f"({j}, {int(c.labels[j])})")
    assert ex.log.commits == 2
    assert eng.stats.rounds == 2          # one apply_model per group commit


def test_plan_errors():
    _c, _catalog, ex = _warm_executor(seed=22)
    with pytest.raises(PlanError):
        ex.execute_one("SELECT label FROM nope WHERE id = 1")
    with pytest.raises(PlanError):
        ex.execute_one("SELECT label FROM v WHERE id = 99999")
    with pytest.raises(PlanError):
        ex.execute_one("CREATE CLASSIFICATION VIEW v2 ON t USING MODEL svm "
                       "WITH (bogus = 1)")
    # k > 1 point label reads must disambiguate the view
    cat2 = Catalog()
    k = 3
    mc = multiclass_corpus("amb", 300, 16, k, seed=19)
    cat2.register_table("m", mc.features, truth=mc.classes, num_classes=k)
    cat2.create_view("w", "m", "svm", {"k": k, "cost_mode": "modeled"})
    ex2 = Executor(cat2, group_commit=4)
    with pytest.raises(PlanError):
        ex2.execute_one("SELECT id, label FROM w WHERE id = 1")
    # ...but view=, the view column, or class all work
    assert ex2.execute_one("SELECT id, view, label FROM w WHERE id = 1").rows
    assert ex2.execute_one(
        "SELECT label FROM w WHERE id = 1 AND view = 2").rows
    assert ex2.execute_one("SELECT id, class FROM w WHERE id = 1").rows


def test_prepared_point_select_equals_direct_and_caches_route():
    """PREPARE/EXECUTE: identical rows to the equivalent SELECT, the plan
    route cached after the first EXECUTE (repeats skip parse+plan), and
    the guards still fire (arity, unknown name, id range)."""
    c, catalog, ex = _warm_executor(seed=44)
    n = c.features.shape[0]
    res = ex.execute_one("PREPARE pt AS SELECT label FROM v WHERE id = ?")
    assert res.rows == [("pt", 1)]
    assert ex.prepared["pt"].plan is None        # planned lazily
    first = ex.execute_one("EXECUTE pt (3)")
    cached = ex.prepared["pt"].plan
    assert cached is not None and cached.kind == "point"
    rng = np.random.default_rng(45)
    for i in rng.integers(0, n, 25):
        got = ex.execute_one(f"EXECUTE pt ({int(i)})").rows
        want = ex.execute_one(f"SELECT label FROM v WHERE id = {int(i)}").rows
        assert got == want, i
    assert ex.prepared["pt"].plan is cached      # route reused, not re-planned
    # read-your-writes still holds on the cached route
    j = int(rng.integers(0, n))
    ex.execute_one(f"INSERT INTO t (id, label) VALUES ({j}, {int(c.labels[j])})")
    got = ex.execute_one(f"EXECUTE pt ({j})").rows[0][0]
    assert got == int(np.sign(0.5 + np.sign(
        c.features[j] @ catalog.view("v").facade.view.model.w
        - catalog.view("v").facade.view.model.b)))
    # programmatic zero-parse path agrees
    assert ex.execute_prepared("pt", [j]).rows == [(got,)]
    from repro.rdbms import SqlError
    with pytest.raises(SqlError):
        ex.execute_one("EXECUTE pt (1, 2)")      # wrong arity
    with pytest.raises(SqlError):
        ex.execute_one("EXECUTE nope (1)")       # unknown name
    with pytest.raises(PlanError):
        ex.execute_one(f"EXECUTE pt ({n + 5})")  # cached route keeps the guard
    with pytest.raises(ParseError):
        ex.execute_one("SELECT label FROM v WHERE id = ?")   # ? needs PREPARE
    with pytest.raises(SqlError):
        ex.execute_one("PREPARE pt AS SELECT label FROM v WHERE id = ?")


def test_prepared_non_point_statements_bind_params():
    c, catalog, ex = _warm_executor(seed=46)
    ex.execute_one("PREPARE cnt AS SELECT count(*) FROM v WHERE label = ?")
    pos = ex.execute_one("EXECUTE cnt (1)").rows[0][0]
    neg = ex.execute_one("EXECUTE cnt (-1)").rows[0][0]
    assert pos + neg == c.features.shape[0]
    from repro.rdbms import SqlError
    with pytest.raises(SqlError):
        ex.execute_one("EXECUTE cnt (2)")        # label must bind to ±1
    ex.execute_one("PREPARE upd AS UPDATE t SET label = ? WHERE id = ?")
    ex.execute_one("EXECUTE upd (1, 5)")
    ex.execute_one("COMMIT")                     # flushes through the WAL
    assert any(r.op == "update" and r.entity_id == 5 for r in ex.log.history)


def test_memory_budget_view_tier_counters_reconcile():
    """SQL acceptance for the storage tier: a hybrid view WITH
    memory_budget answers point SELECTs through water/buffer/pool/disk,
    cold feature reads == the pool's miss count, and SHOW STORAGE renders
    the pool's residency."""
    c = synthetic_corpus("stor", 500, 24, seed=47)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.labels)
    catalog.create_view("v", "t", "svm",
                        {"policy": "hybrid", "p": 2, "q": 2,
                         "buffer_frac": 0.02, "cost_mode": "modeled",
                         "memory_budget": 0.1, "page_bytes": 1024})
    ex = Executor(catalog, group_commit=GROUP)
    facade = catalog.view("v").facade
    n = c.features.shape[0]
    rng = np.random.default_rng(48)
    for _ in range(12):
        rows = [(int(rng.integers(0, n)),) for _ in range(GROUP)]
        ex.execute_one("INSERT INTO t (id, label) VALUES " + ", ".join(
            f"({i}, {int(c.labels[i])})" for (i,) in rows))
    st0 = facade.storage_stats()
    assert st0 is not None and st0["budget_bytes"] == int(0.1 * c.features.nbytes)
    before = dict(facade.tier_hits)
    disk_before = facade.disk_touches
    misses_before = st0["misses"]
    reads = 200
    for _ in range(reads):
        i = int(rng.integers(0, n))
        ex.execute_one(f"SELECT label FROM v WHERE id = {i}")
    hits = {t: facade.tier_hits[t] - before[t] for t in facade.tier_hits}
    assert hits["map"] == 0
    assert (hits["water"] + hits["buffer"] + hits["pool"]
            + hits["disk"]) == reads
    # cold reads are exactly the disk tier; pool hits stayed in memory
    st1 = facade.storage_stats()
    assert facade.disk_touches - disk_before == hits["disk"]
    assert st1["misses"] - misses_before == hits["disk"]
    # the planner advertises the pool in the probe chain
    res = ex.execute_one("EXPLAIN SELECT label FROM v WHERE id = 0")
    assert res.rows[0][1] == "probe(water->buffer->pool->disk)"
    assert res.rows[1][1] in ("water", "buffer", "pool", "disk")
    # SHOW STORAGE renders this view's pool, in-RAM views say so
    catalog.create_view("w", "t", "svm", {"cost_mode": "modeled"})
    show = ex.execute_one("SHOW STORAGE")
    by_name = {r[0]: r for r in show.rows}
    assert by_name["v"][2] == st1["budget_bytes"]
    assert by_name["w"][2] == "in-ram"
    # labels stay exact w.r.t. the current model through the pool
    m = facade.view.model
    truth = np.where(c.features @ m.w - m.b >= 0, 1, -1)
    for i in range(0, n, 17):
        got = ex.execute_one(
            f"SELECT label FROM v WHERE id = {i}").rows[0][0]
        assert got == truth[i]


def test_repl_run_script(capsys):
    from repro.rdbms.repl import run_script
    ex = run_script("""
        CREATE TABLE t FROM CORPUS synthetic WITH (scale = 0.08);
        CREATE CLASSIFICATION VIEW v ON t USING MODEL svm
            WITH (policy = hybrid, cost_mode = modeled);
        INSERT INTO t (id, label) VALUES (0, 1), (1, -1), (2, 1);
        SELECT count(*) FROM v WHERE label = 1;
        SHOW TABLES;
    """)
    out = capsys.readouterr().out
    assert "count" in out and "(1 rows)" in out
    assert "t" in ex.catalog.tables and "v" in ex.catalog.views


# ---------------------------------------------------------------------------
# ISSUE 6: epoch-stamped results + read-your-writes at the executor level
# ---------------------------------------------------------------------------

def test_results_carry_commit_epoch_and_reads_flush_pending():
    """Every Result reports the committed WAL batch index it observed
    (the snapshot version); a read over a table with pending DML flushes
    the group first, so its epoch is the POST-flush index and the
    session's own writes are always visible to its next read."""
    c, catalog, ex = _warm_executor(seed=44)
    epoch0 = ex.log.commits
    assert ex.epoch == epoch0

    # a pending (sub-group) insert: DML reports the epoch after its append
    res = ex.execute_one("INSERT INTO t (id, label) VALUES "
                         f"(5, {int(c.labels[5])})")
    assert res.epoch == epoch0 and ex.log.has_pending("t")

    # the next read flushes first — read-your-writes — and pins AFTER
    r1 = ex.execute_one("SELECT label FROM v WHERE id = 5")
    assert r1.epoch == epoch0 + 1
    assert not ex.log.has_pending("t")

    # reads with nothing pending do not advance anything
    r2 = ex.execute_one("SELECT label FROM v WHERE id = 7")
    assert r2.epoch == ex.log.commits == epoch0 + 1

    # the nested dispatch (EXECUTE -> SELECT) runs inside ONE guard and
    # stamps the same pinned epoch
    ex.execute_one("PREPARE e6 AS SELECT label FROM v WHERE id = ?")
    r3 = ex.execute_one("EXECUTE e6 (7)")
    assert r3.epoch == epoch0 + 1
    assert r3.rows == r2.rows
