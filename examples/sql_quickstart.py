"""SQL quickstart: the paper's workflow end-to-end through the front-end.

Creates a base table from a corpus, registers a hybrid multiclass
classification view on it, streams DML (training inserts through the
group-commit WAL), then reads it back with SELECTs and inspects the
§3.4/§3.5 cost model with EXPLAIN. Every interaction below is a SQL
statement.

Run:  PYTHONPATH=src python examples/sql_quickstart.py
"""
import time

import numpy as np

from repro.rdbms import Executor


def main():
    ex = Executor(group_commit=32)

    # DDL: a base entity table and a model-based view over it. The
    # memory_budget keeps only 10% of the entity table in memory: feature
    # rows live in an on-disk mmap'd EntityStore and probe misses go
    # through a budgeted BufferPool (SHOW STORAGE below shows residency).
    for r in ex.execute("""
        CREATE TABLE papers FROM CORPUS cora_like WITH (scale = 0.5);
        CREATE CLASSIFICATION VIEW topics ON papers USING MODEL svm
            WITH (policy = hybrid, buffer_frac = 0.05, memory_budget = 0.1);
        SHOW VIEWS;
    """):
        print(r.pretty())

    # DML: stream training examples; the WAL group-commits every 32 rows
    # into ONE engine maintenance round ------------------------------------
    t = ex.catalog.table("papers")
    rng = np.random.default_rng(7)
    n_inserts = 600
    t0 = time.perf_counter()
    batch = []
    for _ in range(n_inserts):
        i = int(rng.integers(0, t.n))
        batch.append(f"({i}, {int(t.truth[i])})")
        if len(batch) == 16:          # multi-row INSERT statements
            ex.execute_one(
                f"INSERT INTO papers (id, class) VALUES {', '.join(batch)}")
            batch = []
    if batch:                         # don't drop the last partial batch
        ex.execute_one(
            f"INSERT INTO papers (id, class) VALUES {', '.join(batch)}")
    ex.execute_one("COMMIT")
    dt = time.perf_counter() - t0
    print(f"\nstreamed {n_inserts} training inserts in {dt:.2f}s "
          f"({n_inserts/dt:.0f} rows/s, {ex.log.commits} group commits)")

    # Reads: point lookups, membership scans, counters, top-k margins ------
    probe = int(rng.integers(0, t.n))
    print("\n-- point lookup (all k one-vs-all views of one entity):")
    print(ex.execute_one(
        f"SELECT id, view, label FROM topics WHERE id = {probe}").pretty())

    # Prepared statements: parse+plan once, EXECUTE per read ---------------
    print("\n-- PREPARE/EXECUTE (point reads skip parse AND plan):")
    ex.execute_one(
        "PREPARE pt AS SELECT label FROM topics WHERE id = ? AND view = ?")
    print(ex.execute_one(f"EXECUTE pt ({probe}, 1)").pretty())

    print("\n-- multiclass prediction:")
    print(ex.execute_one(
        f"SELECT id, class FROM topics WHERE id = {probe}").pretty())

    print("\n-- counter read (zero tuples touched):")
    print(ex.execute_one(
        "SELECT count(*) FROM topics WHERE class = 2").pretty())

    print("\n-- membership scan (band partition; only the band touches F):")
    print(ex.execute_one(
        "SELECT id FROM topics WHERE class = 2 LIMIT 5").pretty())

    print("\n-- top-k margins (eps order + Eq. 2 candidate slack):")
    print(ex.execute_one(
        "SELECT id, margin FROM topics WHERE view = 2 "
        "ORDER BY margin DESC LIMIT 5").pretty())

    # EXPLAIN: the §3.4/§3.5 cost model, user-visible ----------------------
    print("\n-- EXPLAIN a point lookup (reports the tier actually used):")
    print(ex.execute_one(
        f"EXPLAIN SELECT label FROM topics WHERE id = {probe} AND view = 1"
    ).pretty())

    print("\n-- EXPLAIN a membership scan:")
    print(ex.execute_one(
        "EXPLAIN SELECT id FROM topics WHERE label = 1 AND view = 1").pretty())

    print("\n-- EXPLAIN a batched insert (group-commit WAL):")
    print(ex.execute_one(
        "EXPLAIN INSERT INTO papers (id, class) VALUES (0, 1)").pretty())

    # SHOW STORAGE: the buffer pool's residency and hit/miss counters ------
    print("\n-- SHOW STORAGE (the 10% memory budget, physically):")
    print(ex.execute_one("SHOW STORAGE").pretty())

    # EXPLAIN ANALYZE: execute for real, annotate the plan with the span
    # tree and the EXACT tier/pool counter deltas the statement caused ----
    print("\n-- EXPLAIN ANALYZE a point lookup (measured spans + tiers):")
    print(ex.execute_one(
        f"EXPLAIN ANALYZE SELECT label FROM topics "
        f"WHERE id = {probe} AND view = 1").pretty())

    # SHOW METRICS: the unified registry — gate, WAL, pools, spans, views --
    print("\n-- SHOW METRICS (a few rows of the unified telemetry ledger):")
    metrics = ex.execute_one("SHOW METRICS")
    wanted = ("counters.", "epoch", "wal.commits")
    print("\n".join(f"  {k} = {v}" for k, v in metrics.rows
                    if any(k.startswith(w) or k == w for w in wanted)))

    # SHOW COST: modeled SKIING charges next to measured wall clock --------
    print("\n-- SHOW COST ON topics (modeled vs measured SKIING):")
    print(ex.execute_one("SHOW COST ON topics").pretty())

    facade = ex.catalog.view("topics").facade
    print(f"\nhybrid tier hits: {facade.tier_hits} "
          f"(cold feature-row reads: {facade.disk_touches})")
    acc = np.mean([facade.predict(i) == int(t.truth[i])
                   for i in range(0, t.n, 5)])
    print(f"prediction agreement with corpus classes: {acc:.3f}")
    assert facade.engine.check_consistent()
    print("view exact w.r.t. current model ✓")

    # Freshness scheduler: a two-level cascade under TARGET_LAG ------------
    # `base` classifies the raw stream; `triage` is a view OVER the view
    # (its single input feature is base's margin column — a DAG edge in
    # the catalog). `base` declares lag 'downstream': it is exactly as
    # fresh as its consumers need, so triage's 2 s lag governs both.
    print("\n-- freshness: a lagged two-level cascade (views over views):")
    for r in ex.execute("""
        CREATE TABLE stream FROM CORPUS synthetic WITH (scale = 0.1);
        CREATE CLASSIFICATION VIEW base ON stream USING MODEL svm
            WITH (cost_mode = modeled, target_lag = downstream);
        CREATE CLASSIFICATION VIEW triage ON base USING MODEL svm
            WITH (cost_mode = modeled, target_lag = '2 s');
        SHOW VIEWS;
    """):
        print(r.pretty())

    st = ex.catalog.table("stream")
    for i in range(0, 48):                # committed, but NOT applied yet:
        ex.execute_one(f"INSERT INTO stream (id, label) VALUES "
                       f"({i}, {int(st.truth[i])})")
    ex.execute_one("COMMIT")
    print("-- SHOW SCHEDULE (the batches queue in the freshness inbox):")
    print(ex.execute_one("SHOW SCHEDULE").pretty())

    # SUSPEND freezes labels; committed updates keep queueing. RESUME
    # catches up exactly once — bit-identical to never having suspended.
    ex.execute_one("ALTER VIEW base SUSPEND")
    for i in range(48, 64):
        ex.execute_one(f"INSERT INTO stream (id, label) VALUES "
                       f"({i}, {int(st.truth[i])})")
    ex.execute_one("COMMIT")
    print("-- suspended:")
    print(ex.execute_one("ALTER VIEW base RESUME").pretty())

    # the refresh barrier: drain every inbox in topological order (in
    # `--serve` mode a background thread does this continuously, picking
    # the most-stale-per-modeled-cost view each slice)
    refreshed = ex.refresh_views()
    print(f"refresh barrier drained (topo order): {refreshed}")
    print(ex.execute_one("SHOW VIEWS").pretty())


if __name__ == "__main__":
    main()
