"""The unified telemetry layer (`repro.obs`) and its surfaces.

Contracts (ISSUE 9):

  * metrics primitives are exact: histogram bucket routing and the
    bucket-edge quantile rule on known distributions, and an 8-thread
    hammer on one registry reconciles to the exact totals;
  * spans nest by the ambient thread-local stack, and `finish` unwinds
    THROUGH a span so an exception path never corrupts later statements;
  * `EXPLAIN ANALYZE` executes the inner statement and its tier row is
    the exact facade `tier_hits` delta — the same counters the registry
    snapshot carries (one ledger, three surfaces);
  * `SHOW METRICS`, the wire `metrics` op, and `Executor.metrics_snapshot`
    agree; `SHOW COST ON v` reports modeled-vs-measured SKIING rows;
  * the slow-statement log fires above the threshold and only above it;
    the server access log emits one line per statement when armed;
  * the REPL footer reports the same span-derived gate-wait/execute split
    the server's elapsed_us carries.
"""
import io
import logging
import threading

import numpy as np
import pytest

from repro.core.facade import TIERS
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Span,
                       ViewCostRecorder, trace)
from repro.rdbms import Catalog, Executor
from repro.rdbms.ast_nodes import SqlError


def _executor(policy="hybrid", **view_opts) -> Executor:
    ex = Executor(group_commit=4)
    ex.execute_one("CREATE TABLE t FROM CORPUS synthetic WITH (scale = 0.05)")
    opts = {"policy": policy, "cost_mode": "modeled", **view_opts}
    with_clause = ", ".join(f"{k} = {v}" for k, v in opts.items())
    ex.execute_one(f"CREATE CLASSIFICATION VIEW v ON t USING MODEL svm "
                   f"WITH ({with_clause})")
    for i in range(8):
        ex.execute_one(f"INSERT INTO t VALUES ({i}, {1 if i % 2 else -1})")
    return ex


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    c, g = Counter(), Gauge()
    c.inc()
    c.inc(41)
    g.set(2.5)
    assert c.value == 42 and g.value == 2.5


def test_histogram_bucket_routing_and_quantiles():
    h = Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    for x in (0.5, 1.0, 1.5, 3.0, 3.0, 7.9, 100.0):
        h.observe(x)
    # inclusive upper edges: 0.5,1.0 -> b0; 1.5 -> b1; 3.0 x2 -> b2;
    # 7.9 -> b3; 100 -> overflow
    assert h.counts == [2, 1, 2, 1, 1]
    assert h.count == 7 and h.sum == pytest.approx(116.9)
    assert h.quantile(0.5) == 4.0          # cum 2,3,5 >= 3.5 at bucket 2
    assert h.quantile(0.99) == float("inf")  # lands in the overflow bucket
    assert h.mean == pytest.approx(116.9 / 7)
    snap = h.snapshot()
    assert snap["count"] == 7 and snap["p50"] == 4.0
    assert snap["p99"] == float("inf") and snap["counts"] == h.counts


def test_histogram_quantile_exact_on_bucket_edges():
    h = Histogram(bounds=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10))
    for x in range(1, 101):
        h.observe((x - 1) % 10 + 1)        # 10 observations per bucket
    assert h.quantile(0.50) == 5
    assert h.quantile(0.99) == 10
    assert h.quantile(0.10) == 1


def test_empty_histogram():
    h = Histogram()
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    assert h.snapshot()["p99"] == 0.0


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.histogram("h") is reg.histogram("h")
    reg.counter("a").inc(3)
    reg.gauge("g").set(7)
    reg.register_collector("comp", lambda: {"x": 1})
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3 and snap["gauges"]["g"] == 7
    assert snap["comp"] == {"x": 1}


def test_registry_collector_errors_are_contained():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("dead component")

    reg.register_collector("bad", boom)
    assert reg.snapshot()["bad"] == {"error": "RuntimeError"}


def test_registry_hammer_reconciles_exactly():
    """8 threads x 5000 ops on ONE registry: counters and histogram
    count/sum land on the exact totals (CPython += is not atomic across
    bytecodes — this is what the per-instrument locks buy)."""
    reg = MetricsRegistry()
    threads_n, ops = 8, 5000

    def work():
        c = reg.counter("hits")
        h = reg.histogram("lat", buckets=(1, 2, 4))
        for i in range(ops):
            c.inc()
            reg.counter("hits")            # get-or-create races too
            h.observe(1 + (i % 3))

    ts = [threading.Thread(target=work) for _ in range(threads_n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    h = reg.histogram("lat")
    assert reg.counter("hits").value == threads_n * ops
    assert h.count == threads_n * ops
    # observations 1,2,3 cycle: buckets (<=1, <=2, <=4) + empty overflow
    expected = [0, 0, 0, 0]
    for i in range(ops):
        expected[i % 3] += threads_n
    assert h.counts == expected
    assert h.sum == pytest.approx(threads_n * sum(1 + (i % 3)
                                                  for i in range(ops)))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_parenting():
    with trace.span("root") as root:
        with trace.span("child", k=1) as c1:
            assert trace.current() is c1
            with trace.span("grand"):
                pass
        with trace.span("child"):
            pass
    assert trace.current() is None
    assert [c.name for c in root.children] == ["child", "child"]
    assert [g.name for g in root.children[0].children] == ["grand"]
    assert root.t1 is not None and root.duration_s >= 0
    assert root.find("grand") is not None
    assert root.sum_us("child") >= root.children[0].children[0].duration_us


def test_span_finish_unwinds_through_exceptions():
    """An exception that leaves children open must not leak them onto the
    ambient stack: finishing the root pops THROUGH the orphans."""
    root = trace.start("root")
    trace.start("orphan1")
    trace.start("orphan2")
    trace.finish(root)
    assert trace.current() is None
    sp = trace.start("fresh")              # a fresh root, not a child
    trace.finish(sp)
    assert root.children[0].name == "orphan1"


def test_span_records_into_registry():
    reg = MetricsRegistry()
    with trace.span("phase", metrics=reg):
        pass
    assert reg.histogram("span.phase.seconds").count == 1


class _Recorder:
    """A span mirror that logs each annotation's enter and exit."""

    def __init__(self, skip=()):
        self.log, self.skip = [], set(skip)

    def __call__(self, name):
        if name in self.skip:
            return None
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", name))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))
        return _Ann()


def test_span_mirror_nests_and_unwinds_through_orphans():
    """The mirror opens at start and closes at finish, innermost first,
    also for children an exception path left open; a factory that returns
    None skips that span; set_mirror returns what it replaced."""
    rec = _Recorder(skip={"quiet"})
    previous = trace.set_mirror(rec)
    try:
        with trace.span("root"):
            with trace.span("child"):
                pass
            with trace.span("quiet"):
                pass
            trace.start("orphan")
        assert trace.set_mirror(rec) is rec
    finally:
        trace.set_mirror(previous)
    assert rec.log == [("enter", "root"), ("enter", "child"),
                       ("exit", "child"), ("enter", "orphan"),
                       ("exit", "orphan"), ("exit", "root")]
    with trace.span("unmirrored") as sp:
        pass
    assert sp.mirror is None and len(rec.log) == 6


def test_mirrored_spans_also_feed_the_profiled_histograms():
    """A span the mirror annotated (the orphan too) is recorded twice:
    `span.<name>.seconds` and `profiled.span.<name>.seconds`; one the
    mirror skipped, or opened with no mirror, only in the first."""
    reg = MetricsRegistry()
    previous = trace.set_mirror(_Recorder(skip={"quiet"}))
    try:
        with trace.span("root", metrics=reg):
            with trace.span("quiet", metrics=reg):
                pass
            orphan = trace.start("orphan")
        trace.finish(orphan, reg)
    finally:
        trace.set_mirror(previous)
    with trace.span("root", metrics=reg):
        pass
    hist = reg.snapshot()["histograms"]
    count = {n: h["count"] for n, h in hist.items()}
    assert count == {"span.root.seconds": 2, "span.quiet.seconds": 1,
                     "span.orphan.seconds": 1,
                     "profiled.span.root.seconds": 1,
                     "profiled.span.orphan.seconds": 1}
    assert hist["profiled.span.root.seconds"]["sum"] <= \
        hist["span.root.seconds"]["sum"]


def test_render_tree_shape():
    with trace.span("a", kind="x") as a:
        with trace.span("b"):
            pass
    text = trace.render_tree(a)
    lines = text.splitlines()
    assert lines[0].startswith("a ") and "[kind=x]" in lines[0]
    assert lines[1].startswith("  b ")


# ---------------------------------------------------------------------------
# ViewCostRecorder
# ---------------------------------------------------------------------------

def test_cost_recorder_snapshot():
    rec = ViewCostRecorder(2)
    rec.record_reorg(0, 0.5)
    rec.record_reorg(0, 1.5)
    rec.record_step(0, 0.25, 2.0)
    rec.record_step(0, 0.75, 2.0)
    s = rec.snapshot(0)
    assert s["reorgs_measured"] == 2
    assert s["S_measured_mean_s"] == pytest.approx(1.0)
    assert s["steps_measured"] == 2
    assert s["charge_modeled"] == pytest.approx(4.0)
    assert s["seconds_measured"] == pytest.approx(1.0)
    assert s["seconds_per_charge"] == pytest.approx(0.25)
    empty = rec.snapshot(1)
    assert empty["steps_measured"] == 0
    assert empty["seconds_per_charge"] is None


# ---------------------------------------------------------------------------
# executor surfaces: statement traces, EXPLAIN ANALYZE, SHOW METRICS/COST
# ---------------------------------------------------------------------------

def test_statement_trace_phases():
    ex = _executor()
    res = ex.execute_one("SELECT id, label FROM v WHERE id = 3")
    assert res.trace is not None and res.trace.name == "statement"
    names = [c.name for c in res.trace.children]
    assert "parse" in names and "execute" in names and "gate.wait" in names
    exec_children = [c.name
                     for c in res.trace.find("execute").children]
    assert "plan" in exec_children and "probe" in exec_children
    assert res.trace.t1 is not None      # finished before it was returned


def test_statement_counters_and_errors():
    ex = _executor()
    before = ex.metrics.counter("statements").value
    errs = ex.metrics.counter("statements.errors").value
    ex.execute_one("SELECT id, label FROM v WHERE id = 1")
    with pytest.raises(SqlError):
        ex.execute_one("SELECT id, label FROM nosuch WHERE id = 1")
    assert ex.metrics.counter("statements").value == before + 2
    assert ex.metrics.counter("statements.errors").value == errs + 1
    assert ex.metrics.counter("statements.select").value >= 2


def test_explain_analyze_tier_row_is_the_exact_facade_delta():
    """The acceptance contract: EXPLAIN ANALYZE on a hybrid point SELECT
    reports tier counts that reconcile EXACTLY with the facade's
    tier_hits deltas (sampled independently here)."""
    ex = _executor(memory_budget=0.25)
    f = ex.catalog.view("v").facade
    before = dict(f.tier_hits)
    res = ex.execute_one(
        "EXPLAIN ANALYZE SELECT id, label FROM v WHERE id IN (1, 2, 3)")
    after = dict(f.tier_hits)
    tier_row = next(r for r in res.rows if r[0] == "tiers")
    reported = dict(kv.split("=") for kv in tier_row[2].split(";"))
    for t in TIERS:
        assert int(reported[t]) == after[t] - before[t], (t, reported)
    assert sum(int(v) for v in reported.values()) == 3
    phases = [r[0].strip() for r in res.rows]
    assert "analyze" in phases and "probe" in phases and "epoch" in phases
    assert next(r for r in res.rows if r[0] == "rows")[2] == "3"


def test_explain_analyze_executes_dml():
    ex = _executor()
    epoch0 = ex.epoch
    queued0 = ex.metrics.counter("wal.appends").value
    ex.execute_one("EXPLAIN ANALYZE INSERT INTO t VALUES (9, 1)")
    assert ex.metrics.counter("wal.appends").value == queued0 + 1
    # plain EXPLAIN never mutates
    ex.execute_one("EXPLAIN INSERT INTO t VALUES (10, 1)")
    assert ex.metrics.counter("wal.appends").value == queued0 + 1
    assert ex.epoch >= epoch0


def test_explain_analyze_flushes_read_your_writes():
    ex = _executor()
    ex.execute_one("INSERT INTO t VALUES (11, 1)")
    assert ex.log.has_pending("t")
    ex.execute_one("EXPLAIN ANALYZE SELECT id, label FROM v WHERE id = 11")
    assert not ex.log.has_pending("t")


def test_show_metrics_and_snapshot_agree():
    ex = _executor()
    res = ex.execute_one("SHOW METRICS")
    flat = dict(res.rows)
    snap = ex.metrics_snapshot()
    assert res.columns == ("metric", "value")
    assert flat["epoch"] == snap["epoch"] == ex.log.commits
    assert flat["counters.wal.commits"] == \
        snap["counters"]["wal.commits"] == ex.log.commits
    assert flat["counters.gate.exclusive_acquisitions"] == \
        snap["counters"]["gate.exclusive_acquisitions"]
    # per-view collector rides along
    assert flat["view.v.policy"] == "hybrid"
    # the SHOW itself was gated + counted by the time we snapshot again
    assert ex.metrics.counter("statements.show").value >= 1


def test_gate_wait_histograms_populated():
    ex = _executor()
    ex.execute_one("SELECT id, label FROM v WHERE id = 1")
    snap = ex.metrics_snapshot()
    assert snap["histograms"]["gate.shared_wait_seconds"]["count"] >= 1
    assert snap["histograms"]["gate.exclusive_wait_seconds"]["count"] >= 8
    assert snap["counters"]["gate.shared_acquisitions"] >= 1


def test_show_cost_reports_modeled_vs_measured():
    ex = _executor()
    ex.execute_one("UPDATE MODEL ON v")
    res = ex.execute_one("SHOW COST ON v")
    assert res.columns[0] == "view"
    row = dict(zip(res.columns, res.rows[0]))
    assert row["view"] == "v" and row["cost_mode"] == "modeled"
    assert int(row["reorgs"]) >= 1
    assert float(row["S_measured_mean_s"]) > 0    # wall clock, measured
    if int(row["steps"]) and float(row["charge_modeled"]) > 0:
        assert float(row["seconds_per_charge"]) > 0


def test_show_cost_multiview_and_unknown_view():
    ex = Executor()
    ex.execute_one("CREATE TABLE m FROM CORPUS cora_like WITH (scale = 0.05)")
    ex.execute_one("CREATE CLASSIFICATION VIEW mv ON m USING MODEL svm "
                   "WITH (k = 7, policy = hybrid, cost_mode = modeled)")
    for i in range(6):
        ex.execute_one(f"INSERT INTO m VALUES ({i}, {i % 7})")
    ex.execute_one("UPDATE MODEL ON mv")
    res = ex.execute_one("SHOW COST ON mv")
    assert len(res.rows) == 7
    assert [r[1] for r in res.rows] == list(range(7))
    with pytest.raises(SqlError):
        ex.execute_one("SHOW COST ON nosuch")


def test_slow_log_fires_above_threshold_only(caplog):
    ex = _executor()
    with caplog.at_level(logging.WARNING, logger="repro.obs.slowlog"):
        ex.slow_ms = 1e9                   # nothing is this slow
        ex.execute_one("SELECT id, label FROM v WHERE id = 1")
        assert not caplog.records
        ex.slow_ms = 0.0                   # everything is slower than 0
        ex.execute_one("SELECT id, label FROM v WHERE id = 2")
    assert len(caplog.records) == 1
    msg = caplog.records[0].getMessage()
    assert "slow statement" in msg and "statement" in msg and "probe" in msg


def test_pool_read_spans_feed_registry():
    ex = _executor(memory_budget=0.1)
    ex.execute_one("SELECT id, label FROM v WHERE label = 1")  # band scan
    snap = ex.metrics_snapshot()
    st = snap["view.v"]["storage"]
    assert st["hits"] + st["misses"] + st["coalesced"] == st["probes"]
    if st["misses"]:                       # cold reads went through spans
        assert snap["histograms"]["span.pool.read.seconds"]["count"] >= 1


def test_wal_telemetry_counters():
    ex = _executor()
    snap = ex.metrics_snapshot()
    assert snap["wal"]["commits"] == ex.log.commits == snap["epoch"]
    assert snap["counters"]["wal.appends"] == 8
    assert snap["histograms"]["wal.group_size"]["count"] == ex.log.commits


# ---------------------------------------------------------------------------
# server + wire + REPL surfaces
# ---------------------------------------------------------------------------

@pytest.fixture()
def served():
    from repro.rdbms import SqlClient, start_server_thread
    ex = _executor()
    handle = start_server_thread(ex, log_statements=True)
    host, port = handle.address
    client = SqlClient.connect(host, port)
    yield ex, client
    client.close()
    handle.stop()


def test_wire_metrics_roundtrip(served):
    ex, client = served
    client.query("SELECT id, label FROM v WHERE id = 1")
    snap = client.metrics()
    assert snap["epoch"] == ex.log.commits
    assert snap["counters"]["statements"] >= 1
    assert "view.v" in snap and snap["view.v"]["policy"] == "hybrid"
    # JSON round trip: histograms arrive as plain dicts
    assert isinstance(snap["histograms"]["statement.seconds"]["p99"],
                      (int, float))


def test_wire_results_carry_span_phases(served):
    _, client = served
    r = client.query_one("SELECT id, label FROM v WHERE id = 2")
    assert r.elapsed_us is not None and r.elapsed_us > 0
    assert "execute" in r.phases and "gate.wait" in r.phases
    assert client.last_elapsed_us is not None


def test_access_log_line_per_statement(served, caplog):
    _, client = served
    with caplog.at_level(logging.INFO, logger="repro.rdbms.server"):
        client.query("SELECT id, label FROM v WHERE id = 1; "
                     "SELECT id, label FROM v WHERE id = 2")
    lines = [r.getMessage() for r in caplog.records
             if "kind=select" in r.getMessage()]
    assert len(lines) == 2
    assert all("session=" in ln and "elapsed_us=" in ln and "epoch=" in ln
               for ln in lines)


def test_repl_footer_reports_gate_and_execute_split():
    from repro.rdbms.repl import repl
    ex = _executor()
    out = io.StringIO()
    repl(ex, stdin=io.StringIO("SELECT id, label FROM v WHERE id = 1;\n"),
         out=out)
    text = out.getvalue()
    footer = next(ln for ln in text.splitlines()
                  if ln.startswith("-- ") and "gate-wait" in ln)
    assert "ms (gate-wait" in footer and "execute" in footer


def test_telemetry_overhead_is_bounded():
    """The armed registry must not dominate statement cost: a counter inc
    plus a histogram observe is well under a microsecond-scale statement.
    (The real p99 gate runs in CI serve-smoke; this is the unit guard.)"""
    from repro.obs import clock
    reg = MetricsRegistry()
    c = reg.counter("x")
    h = reg.histogram("y")
    t0 = clock()
    for _ in range(10000):
        c.inc()
        h.observe(1e-4)
    per_op = (clock() - t0) / 10000
    assert per_op < 50e-6                  # generous: CI boxes are noisy


# ---------------------------------------------------------------------------
# the device engine's spans, programs and compile counters
# ---------------------------------------------------------------------------

def _sharded_executor(n=256, d=16, k=4, rounds=10, seed=9):
    """A served-path stack over engine = sharded on a (1, 1) CPU mesh: the
    driver installs the profiler mirror and counts compiles in the
    catalog's registry. Runs `rounds` group commits, each followed by
    point reads of every 16th entity."""
    pytest.importorskip("jax")
    from repro.data import multiclass_corpus
    c = multiclass_corpus("obs", n, d, k, seed=seed)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.classes, num_classes=k)
    catalog.create_view("v", "t", "svm", {"engine": "sharded", "k": k,
                                          "p": 2, "q": 2, "lr": 0.5,
                                          "cap_frac": 0.5})
    ex = Executor(catalog, group_commit=8)
    rng = np.random.default_rng(seed)

    def drive():
        for _ in range(rounds):
            ids = rng.integers(0, n, 8)
            ex.execute_one("INSERT INTO t (id, class) VALUES " + ", ".join(
                f"({int(i)}, {int(c.classes[i])})" for i in ids))
            for i in range(0, n, 16):
                ex.execute_one(f"SELECT id, view, label FROM v WHERE id = {i}")
    return ex, catalog.view("v").facade, drive


def test_round_and_read_span_counts_reconcile_with_driver_counters():
    ex, f, drive = _sharded_executor()
    drive()
    d = f.driver
    hist = ex.metrics_snapshot()["histograms"]

    def count(name):
        return hist.get(f"span.{name}.seconds", {"count": 0})["count"]
    assert d.kernel_rounds > d.overflows and d.skiing.reorgs > 0
    assert f.disk_touches > 0
    assert count("round.update") == d.kernel_rounds
    assert count("round.sync") == d.kernel_rounds
    assert count("round.reorganize") == d.skiing.reorgs
    assert count("read.margin") == 0        # the probe program takes the row
    assert count("read.probe") == 16 * 10
    assert count("round.sgd") == ex.log.commits == 10
    # a round either dispatches the band update or SKIING reorganizes it
    assert count("round.fetch") == count("round.waters") == d.kernel_rounds
    assert d.kernel_rounds + d.skiing.reorgs - d.overflows == 10


def test_wide_band_rounds_relabel_without_reorganizing():
    """Rounds whose bands cover more than half the table relabel through
    the band kernel alone: no reorganize (SKIING's threshold is high), no
    overflow, labels equal to a from-scratch relabel, and
    `band.window_rows` takes one observation a launch, in SHOW METRICS."""
    pytest.importorskip("jax")
    from repro.data import multiclass_corpus
    n, d, k = 1000, 16, 4
    c = multiclass_corpus("wide", n, d, k, seed=2)
    catalog = Catalog()
    catalog.register_table("t", c.features, truth=c.classes, num_classes=k)
    catalog.create_view("v", "t", "svm", {"engine": "sharded", "k": k,
                                          "p": 2, "q": 2, "lr": 0.5,
                                          "alpha": 1000.0})
    ex = Executor(catalog, group_commit=8)
    f = catalog.view("v").facade
    drv = f.driver
    rng = np.random.default_rng(2)
    for _ in range(4):
        ids = rng.integers(0, n, 8)
        ex.execute_one("INSERT INTO t (id, class) VALUES " + ", ".join(
            f"({int(i)}, {int(c.classes[i])})" for i in ids))
    # every round went to the kernel, and its bands covered over half
    assert drv.kernel_rounds == 4 and drv.skiing.reorgs == 0
    assert drv.overflows == 0
    assert drv.skiing.total_incremental / drv.kernel_rounds > 0.5
    gids, labels, _ = drv.real_rows(f.state)
    z = f.F[gids] @ f.W.T - f.b.astype(np.float32)
    settled = np.abs(z) > 1e-4
    assert np.array_equal(labels.T[settled], np.where(z >= 0, 1, -1)[settled])
    hist = ex.metrics_snapshot()["histograms"]["band.window_rows"]
    assert hist["count"] == drv.kernel_rounds
    assert drv.n_pad / 2 < hist["sum"] / hist["count"] <= drv.n_pad
    flat = dict(ex.execute_one("SHOW METRICS").rows)
    assert flat["histograms.band.window_rows.count"] == drv.kernel_rounds


def test_profiler_trace_holds_nested_statement_and_round_spans(tmp_path):
    """With the mirror installed (by the driver), a profile collected on
    the CPU holds the statement spans and the round.* and read.* spans,
    each inside its parent on the same thread."""
    jax = pytest.importorskip("jax")
    ex, f, drive = _sharded_executor(rounds=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    parent = {"statement": None, "execute": "statement",
              "wal.commit": "execute", "probe": "execute",
              "round.sgd": "wal.commit", "round.fetch": "wal.commit",
              "round.waters": "wal.commit", "round.update": "wal.commit",
              "round.sync": "wal.commit", "round.reorganize": "wal.commit",
              "read.probe": "probe"}
    seen = {}
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name in parent]
            for name, s, e in evs:
                seen[name] = seen.get(name, 0) + 1
                up = parent[name]
                if up is not None:
                    assert any(n == up and ps <= s and e <= pe
                               for n, ps, pe in evs), (name, s)
    assert {"statement", "wal.commit", "round.sgd", "round.update",
            "round.sync", "read.probe"} <= set(seen)
    assert seen["round.update"] + seen.get("round.reorganize", 0) >= 4
    # the driver's spans of the profiled window, counted by the program
    hist = ex.metrics_snapshot()["histograms"]
    for name in ("round.sgd", "round.update", "round.sync", "read.probe"):
        assert hist[f"profiled.span.{name}.seconds"]["count"] == seen[name]
    assert "profiled.span.read.probe.seconds" not in \
        _sharded_executor(rounds=1)[0].metrics_snapshot()["histograms"]


def test_programs_carry_their_names_in_compile_events():
    """Every jitted program of the driver compiles under its own name,
    and the driver counts those compiles in its registry."""
    jax = pytest.importorskip("jax")
    from repro.core import sharded
    from repro.core.waters import holder_M
    from repro.launch.mesh import make_host_mesh

    names = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        rng = np.random.default_rng(5)
        n, d, k = 256, 16, 3
        F = rng.random((n, d), dtype=np.float32)
        reg = MetricsRegistry()
        drv = sharded.ShardedMultiViewHazy(
            mesh=make_host_mesh((1, 1)), n=n, d=d, k=k, M=holder_M(F, 2.0),
            metrics=reg)
        state = drv.init_state(F)
        W = rng.normal(size=(k, d)).astype(np.float32) * 0.01
        b = np.zeros(k)
        state = drv.apply_models(state, W, b)
        drv.lw[:], drv.hw[:] = -1e9, 1e9      # every view misses
        drv.hybrid_labels_of(state, 3)
        drv.all_members(state)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    want = {"band_update", "reorganize", "probe", "all_members"}
    assert {f"jit({p})" for p in want} <= set(names)
    counters = reg.snapshot()["counters"]
    assert all(counters[f"compiles.{p}"] >= 1 for p in want)
    assert reg.histogram("compiles.band_update.seconds").sum > 0
