#!/usr/bin/env python3
"""Chip smoke test: the served SQL path on one TPU at Citeseer size.

Builds the Citeseer-shaped entity table at the paper's row count (721,000
rows through the hashing trick), registers it in a catalog, starts the
wire server in this process and drives it through `SqlClient`:

  * CREATE CLASSIFICATION VIEW ... WITH (engine = sharded, k = 16) — the
    device engine, whose band relabels run the Pallas kernel;
  * a few hundred training INSERTs in group commits (one maintenance round
    each: SGD, waters, then the kernel or a reorganize);
  * point SELECTs, COUNT(*) for every view and one members scan.

Every answer is checked against a plain numpy reference: labels =
sign(F·Wᵀ − b) in f32 under the view's current model. A (row, view) pair
whose reference margin is within `TOL` of zero may round either way, so
only pairs outside it must match; the script prints how many fall inside.

    python3 chip_smoke.py                  # one TPU chip
    python3 chip_smoke.py --four-chips     # the row-sharded engine on a
                                           # (4, 1) mesh vs one chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal   # tiny, CPU

The last line of standard output is one JSON object, {"ok": true,
"device": {"platform": ..., "kind": ..., "count": ...}}, printed only when
every phase passed. Without a TPU, and without --cpu-rehearsal, the script
exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
K = 16
HASH_DIM = 1024           # citeseer_like's default is 4096 (see CUT)
CUT = ("cut: hash width 4096 -> 1024 features; at 4096 the f32 table is "
       "11.8 GB and reorganize holds a second copy, which cannot fit one "
       "16 GB chip. Rows (721,000) are the paper's; k = 16 and lr = 1.0 "
       "(the engine's default is 0.1, too small for rows of unit L1 norm "
       "to move a decision boundary within a few hundred inserts).")
TOL = 1e-4                # |reference margin| at or below this may round
                          # to either sign between summation orders
VIEW_OPTS = dict(lr=1.0, l2=1e-4, p=2.0, q=2.0, alpha=1.0)
ROUNDS, GROUP = 24, 16    # maintenance rounds x inserts per group commit
REHEARSAL_SCALE = 0.001   # --cpu-rehearsal: 1,000 rows


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_table(scale: float, seed: int):
    """Citeseer-shaped features plus k one-vs-all classes drawn from the
    seed. Class 0 is the corpus's own topic (its hidden halfspace, about
    half the rows), so view 0 has a real decision boundary that moves
    through the table while it trains; the other rows draw one of classes
    1..k-1 uniformly."""
    from repro.data import citeseer_like
    corpus = citeseer_like(scale=scale, hash_dim=HASH_DIM, seed=seed)
    rng = np.random.default_rng(seed)
    n = corpus.features.shape[0]
    classes = np.where(corpus.labels > 0, 0, rng.integers(1, K, n))
    return corpus.features, classes, rng


def make_stream(rng, n: int, rounds: int, group: int):
    return [rng.integers(0, n, group) for _ in range(rounds)]


def reference_margins(F, W, b) -> np.ndarray:
    """(n, k) f32 margins F·Wᵀ − b under the current model (numpy)."""
    return F @ np.asarray(W, np.float32).T - np.asarray(b, np.float32)


def labels_by_entity(gids, labels, n: int) -> np.ndarray:
    """(k, n) device labels scattered from the shared order to entity
    order; real rows only (`gids` from `ShardedMultiViewHazy.real_rows`)."""
    check(gids.size == n and np.array_equal(np.sort(gids), np.arange(n)),
          "device table does not hold each real entity exactly once")
    out = np.empty((labels.shape[0], n), np.int8)
    out[:, gids] = labels
    return out


def compare(name: str, got: np.ndarray, Z: np.ndarray) -> int:
    """got: (k, n) labels in entity order; Z: (n, k) reference margins.
    Fails on any mismatch outside TOL; returns the mismatches inside."""
    ref = np.where(Z.T >= 0, 1, -1)
    near = np.abs(Z.T) <= TOL
    bad = got != ref
    far_bad = int(np.count_nonzero(bad & ~near))
    near_bad = int(np.count_nonzero(bad & near))
    print(f"{name}: {got.size:,} (row, view) pairs; {int(near.sum()):,} "
          f"within |z| <= {TOL:g}; mismatches outside tol {far_bad}, "
          f"inside tol {near_bad}")
    check(far_bad == 0, f"{name}: {far_bad} labels disagree with the "
          f"reference outside the tolerance")
    return near_bad


class CompileTimer:
    """Backend compile seconds and count, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def peak_memory(devices) -> str:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        peaks.append("n/a" if peak is None else f"{peak / 1e9:.3f} GB")
    return ", ".join(peaks)


def serve_and_check(scale: float, rounds: int, group: int, seed: int,
                    devices) -> None:
    """The served path on one device, checked against the reference."""
    from repro.rdbms import Catalog, Executor
    from repro.rdbms.client import ServerError, SqlClient
    from repro.rdbms.server import start_server_thread

    t0 = time.perf_counter()
    F, classes, rng = make_table(scale, seed)
    n, d = F.shape
    print(f"table: n = {n:,} real rows, d = {d}, k = {K}, f32 "
          f"{F.nbytes / 1e9:.3f} GB, built in "
          f"{time.perf_counter() - t0:.1f} s")
    stream = make_stream(rng, n, rounds, group)
    catalog = Catalog()
    catalog.register_table("papers", F, truth=classes, num_classes=K)
    ex = Executor(catalog, group_commit=group)
    server = start_server_thread(ex)
    statements = 0

    def run(client, sql):
        nonlocal statements
        statements += 1
        try:
            return client.run_one(sql)
        except ServerError as e:
            raise SmokeFailure(f"statement error: {e} in {sql[:80]!r}")

    try:
        with SqlClient.connect(*server.address, timeout=1200) as c, \
                CompileTimer() as ct:
            opts = ", ".join(f"{k} = {v}" for k, v in VIEW_OPTS.items())
            t = time.perf_counter()
            run(c, "CREATE CLASSIFICATION VIEW topics ON papers USING MODEL "
                   f"svm WITH (engine = sharded, k = {K}, {opts})")
            print(f"create view: {time.perf_counter() - t:.2f} s (table to "
                  f"device + first reorganize, compile included)")
            driver = catalog.view("topics").facade.driver
            print(f"layout: n_pad = {driver.n_pad:,}, block_n = "
                  f"{driver.block_n}, rows a shard = {driver.cap:,}, "
                  f"mesh {dict(driver.mesh.shape)}")

            round_s = []
            for r, ids in enumerate(stream):
                rows = ", ".join(f"({int(i)}, {int(classes[i])})"
                                 for i in ids)
                compiles = ct.count
                t = time.perf_counter()
                run(c, f"INSERT INTO papers (id, class) VALUES {rows}")
                round_s.append(time.perf_counter() - t)
                print(f"round {r}: {len(ids)} inserts, {round_s[-1]:.4f} s, "
                      f"kernel rounds {driver.kernel_rounds}, reorganizes "
                      f"{driver.skiing.reorgs}, compiles "
                      f"{ct.count - compiles}")
            kernel_ok = driver.kernel_rounds
            print(f"maintenance: {len(stream)} rounds of {group} inserts; "
                  f"kernel rounds {kernel_ok}, reorganizes "
                  f"{driver.skiing.reorgs}; "
                  f"seconds per round: first {round_s[0]:.4f}, median of "
                  f"the rest {float(np.median(round_s[1:])):.4f}, max "
                  f"{max(round_s[1:]):.4f}")
            check(kernel_ok >= 3, "fewer than 3 kernel maintenance rounds")
            check(driver.skiing.reorgs >= 1, "no reorganize ran")

            point_ids = rng.integers(0, n, 32)
            points = {}
            t = time.perf_counter()
            for i in point_ids:
                res = run(c, f"SELECT id, view, label FROM topics "
                             f"WHERE id = {int(i)}")
                points[int(i)] = {int(v): int(lab) for _, v, lab in res.rows}
            point_s = time.perf_counter() - t
            counts = [run(c, f"SELECT count(*) FROM topics "
                             f"WHERE class = {v}").rows[0][0]
                      for v in range(K)]
            # both sides of the view with the most members: together they
            # must list every real entity once and no padding row
            scan_view = int(np.argmax(counts))
            scans = [np.array([row[0] for row in run(
                c, f"SELECT id FROM topics WHERE class = {scan_view} "
                   f"AND label = {lab}").rows], np.int64) for lab in (1, -1)]
            print(f"reads: {len(point_ids)} point selects in {point_s:.3f} "
                  f"s, {K} counts {counts}, members scans of view "
                  f"{scan_view}: {scans[0].size:,} positive, "
                  f"{scans[1].size:,} negative ids")
            print(f"compile: {ct.count} backend compiles, "
                  f"{ct.seconds:.2f} s")
    finally:
        server.stop()

    facade = catalog.view("topics").facade
    t = time.perf_counter()
    Z = reference_margins(F, facade.W, facade.b)
    gids, labels, eps = facade.driver.real_rows(facade.state)
    got = labels_by_entity(gids, labels, n)
    compare("reference labels", got, Z)
    ref = np.where(Z >= 0, 1, -1)
    near = np.abs(Z) <= TOL
    dev_counts = (got == 1).sum(axis=1)
    check(np.array_equal(counts, dev_counts),
          f"COUNT(*) {counts} != device labels {dev_counts.tolist()}")
    slack = np.abs(np.asarray(counts) - (ref == 1).sum(axis=0))
    check(np.all(slack <= near.sum(axis=0)),
          "COUNT(*) disagrees with the reference beyond near-zero rows")
    for i, got_i in points.items():
        for v in range(K):
            check(near[i, v] or got_i[v] == ref[i, v],
                  f"point SELECT id {i} view {v}: {got_i[v]} != {ref[i, v]}")
    members = scans[0]
    check(np.array_equal(np.sort(np.concatenate(scans)), np.arange(n)),
          "the two members scans do not list each real entity exactly once")
    check(np.array_equal(np.sort(members),
                         np.flatnonzero(got[scan_view] == 1)),
          "members scan != the device labels of that view")
    diff = np.setxor1d(members, np.flatnonzero(ref[:, scan_view] == 1))
    check(np.all(near[diff, scan_view]),
          "members scan disagrees with the reference beyond near-zero rows")
    # the stored margins decide which rows the next rounds may skip: they
    # must be f32-accurate under the stored model
    Zs = reference_margins(F, np.asarray(facade.state.W_stored),
                           np.asarray(facade.state.b_stored))
    err = float(np.max(np.abs(eps - Zs[gids].T)))
    print(f"stored eps vs reference under the stored model: max abs error "
          f"{err:.3g}")
    check(err <= TOL, "stored eps are not f32-accurate")
    print(f"counts, {len(points)} point reads and the members scan agree "
          f"with the reference (checked in {time.perf_counter() - t:.1f} s)")
    print(f"statements: {statements}, statement errors: 0")
    print(f"peak device memory: {peak_memory(devices)}")


def four_chips(scale: float, rounds: int, group: int, seed: int,
               devices) -> None:
    """The same stream through `ShardedMultiViewHazy` on a (4, 1)
    ("data", "model") mesh over all four devices and on one device, both
    compared with the reference and with each other."""
    from repro.core.multiclass import sgd_all_views
    from repro.core.sharded import ShardedMultiViewHazy
    from repro.core.waters import holder_M
    from repro.launch.mesh import make_mesh

    check(len(devices) == 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    F, classes, rng = make_table(scale, seed)
    n, d = F.shape
    stream = make_stream(rng, n, rounds, group)
    print(f"table: n = {n:,} real rows, d = {d}, k = {K}")
    M = holder_M(F, VIEW_OPTS["q"])
    kw = dict(n=n, d=d, k=K, M=M, p=VIEW_OPTS["p"],
              alpha=VIEW_OPTS["alpha"])
    runs = {"4 chips": ShardedMultiViewHazy(
                mesh=make_mesh((4, 1), ("data", "model")), **kw),
            "1 chip": ShardedMultiViewHazy(
                mesh=make_mesh((1, 1), ("data", "model")), **kw)}
    states = {}
    for name, dr in runs.items():
        t = time.perf_counter()
        states[name] = dr.init_state(F)
        shards = [(s.device.id, s.data.shape)
                  for s in states[name].F.addressable_shards]
        print(f"{name}: F shards {shards}, init {time.perf_counter() - t:.2f} "
              f"s; n_pad = {dr.n_pad:,}, block_n = {dr.block_n}, rows a "
              f"shard = {dr.cap:,}")
    check(len({s.device for s in states["4 chips"].F.addressable_shards})
          == 4, "the 4-chip table is not spread over four devices")

    W = np.zeros((K, d), np.float32)
    b = np.zeros(K, np.float64)
    for r, ids in enumerate(stream):
        for i in ids:
            W, b = sgd_all_views(W, b, F[int(i)], int(classes[i]),
                                 lr=VIEW_OPTS["lr"], l2=VIEW_OPTS["l2"])
        line = []
        for name, dr in runs.items():
            t = time.perf_counter()
            states[name] = dr.apply_models(states[name], W, b)
            states[name].labels.block_until_ready()
            line.append(f"{name} {time.perf_counter() - t:.4f} s")
        print(f"round {r}: " + ", ".join(line))

    Z = reference_margins(F, W, b)
    got = {}
    for name, dr in runs.items():
        kernel_ok = dr.kernel_rounds
        print(f"{name}: kernel rounds {kernel_ok}, reorganizes "
              f"{dr.skiing.reorgs}, counts "
              f"{dr.all_members(states[name]).tolist()}")
        check(kernel_ok >= 3, f"{name}: fewer than 3 kernel rounds")
        gids, labels, _ = dr.real_rows(states[name])
        got[name] = labels_by_entity(gids, labels, n)
        compare(f"{name} vs reference", got[name], Z)
        check(np.array_equal(dr.all_members(states[name]),
                             (got[name] == 1).sum(axis=1)),
              f"{name}: counts != its labels")
    near = np.abs(Z.T) <= TOL
    differ = got["4 chips"] != got["1 chip"]
    far = int(np.count_nonzero(differ & ~near))
    print(f"4 chips vs 1 chip: {int(differ.sum())} labels differ, "
          f"{far} of them outside tol")
    check(far == 0, "the 4-chip and 1-chip runs disagree outside tol")
    print(f"peak device memory: {peak_memory(devices)}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the (4, 1)-mesh comparison phase only")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny table on the CPU (interpret-mode kernel)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke.py: the repro package is not beside this script "
              "(run it from the repository root)", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev.platform != want:
        print(f"chip_smoke.py: JAX found {dev.platform!r} devices, this run "
              f"needs {want!r}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}")
    print(CUT)
    scale = REHEARSAL_SCALE if args.cpu_rehearsal else 1.0
    if args.cpu_rehearsal:
        print(f"rehearsal: scale {scale} of the paper's rows, on the CPU")
    phase = four_chips if args.four_chips else serve_and_check
    t = time.perf_counter()
    try:
        phase(scale, ROUNDS, GROUP, args.seed,
              devices if args.four_chips else devices[:1])
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
