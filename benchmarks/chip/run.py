#!/usr/bin/env python3
"""Chip benchmark of the served classification-view server: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is one entry of `workloads` in BENCHMARK.json: a configuration
(`configs/<config>.json`, its table built by `data/<generator>.py`) under a
traffic mix (`traffic/<mix>.json`, read by `load.py`). The run

  1. builds the table from the seed, registers it in a `Catalog`, starts
     the SQL wire server (`start_server_thread`) in this process and
     creates the views over the wire with `engine = sharded`;
  2. warms up: training commits until the update and reorganize programs
     have run, with point reads after the first round that leaves rows
     inside the waters, until the probe and margin programs have run;
     set-up ends here;
  3. measures for --seconds with the mix's sessions (threads of this
     process); with --trace 1 under the JAX profiler;
  4. checks every committed model, label, stored margin and served answer
     against the plain numpy reference (`reference.py`).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
--trace 1 its per-layer metrics, each read by `layers/<metric>.py`),
`device`, `breakdown` (traced runs) and `checks`, the numbers compared with
their limits. The same checks are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional, Sequence  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import load  # noqa: E402
import need  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

clock = time.perf_counter
KERNEL = "multiview_band_reclassify"   # the band kernel's op in the trace
EXACT_CHECKS = ("model_err", "label_mismatch", "answer_mismatch",
                "wal_mismatch", "ack_bad", "hung_sessions")


class RunError(Exception):
    """The run could not be completed as the cell asks; no result."""


def module_from(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_dir(root: Path = ROOT) -> Path:
    """This directory's place in a checkout rooted at `root`: where the
    mix and the limits of a cell are found."""
    return root / HERE.relative_to(ROOT)


def load_cell(name: str, root: Path = ROOT):
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    bench = bench_dir(root)
    traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{cell['config']}.json")
                        .read_text())
    return manifest, cell, cfg, traffic, limits


def reported(entries, cell: str):
    return [m for m in entries if cell in m.get("workloads", [cell])]


class GcPauses:
    """Python's cyclic garbage collections and their seconds, by
    generation, while registered."""

    def __init__(self):
        self.count, self.seconds, self._t = [0, 0, 0], [0.0, 0.0, 0.0], 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = clock()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += clock() - self._t


class CompileCounter:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


class Run:
    """One run of one cell. Attributes read by `layers/*.py`: the window
    (`t0`, `t1`, `seconds`), `window_commits`, `window_reads`, `wal_delta`
    (count, sum of `span.wal.commit.seconds`), `counter_delta` (kernel
    rounds, overflows, reorganizes), `tier_delta`, `trace` (the reduced
    trace, traced runs only), `launch_need_s` (per band-kernel round in
    the window, the seconds its need takes at one chip's peak bandwidth)
    and `cell` (its `chips`)."""

    def __init__(self, cell, cfg, traffic, seed: int, seconds: float,
                 trace: bool, peaks: Optional[dict], rows: Optional[int],
                 warm_scale: float = 1.0, log=print):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.traced = seed, seconds, trace
        self.peaks = peaks
        self.rows = rows if rows is not None else int(cfg["table"]["rows"])
        self.warm_scale = warm_scale
        self.log = log
        self.trace = None
        self.launch_need_s = None

    # -- set-up ---------------------------------------------------------
    def setup(self):
        self.log(f"process start to set-up: {clock() - T_START:.3f} s "
                 f"(imports, JAX's device start)")
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from repro.rdbms import Catalog, Executor
        from repro.rdbms.client import SqlClient
        from repro.rdbms.server import start_server_thread
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.compiles = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        cfg, traffic = self.cfg, self.traffic
        k = int(cfg["k"])
        ss = np.random.SeedSequence(self.seed % (1 << 64))
        rng_table, rng_traffic = (np.random.default_rng(s)
                                  for s in ss.spawn(2))
        t = clock()
        gen = module_from(HERE / "data" / f"{cfg['generator']}.py")
        self.F, self.truth = gen.make(cfg["table"], k, rng_table, self.rows)
        self.table_s = clock() - t
        self.n, self.d = self.F.shape
        t = clock()
        self.plan = load.make_plan(traffic, self.n, k, rng_traffic)
        self.log(f"traffic plan drawn in {clock() - t:.3f} s")
        self.log(f"table: {self.n:,} rows x {self.d} f32 "
                 f"({self.F.nbytes / 1e9:.3f} GB), k = {k}, built in "
                 f"{self.table_s:.3f} s; compile cache {cache}")

        self.tname = cfg["table"]["name"]
        self.vname = cfg["view"]
        catalog = Catalog()
        catalog.register_table(self.tname, self.F, truth=self.truth,
                               num_classes=k)
        self.ex = Executor(catalog, group_commit=int(traffic["rows_per_commit"]))
        self.server = start_server_thread(self.ex)
        self.admin = SqlClient.connect(*self.server.address, timeout=1200)
        opts = ", ".join(f"{a} = {v}" for a, v in cfg["view_options"].items())
        t = clock()
        self.admin.run_one(f"CREATE CLASSIFICATION VIEW {self.vname} ON "
                           f"{self.tname} USING MODEL svm WITH (k = {k}, "
                           f"{opts})")
        self.create_s = clock() - t
        self.facade = catalog.view(self.vname).facade
        self.driver = self.facade.driver
        self.log(f"create view: {self.create_s:.3f} s; n_pad = "
                 f"{self.driver.n_pad:,}, block_n = {self.driver.block_n}, "
                 f"kernel window cap = {self.driver.cap:,}")
        self.commits = []                   # every commit, in epoch order
        self.reads = []                     # every served read
        t = clock()
        self._warm_commits()
        self.log(f"warm-up: {clock() - t:.3f} s")
        self.setup_compiles = (self.compiles.count, self.compiles.seconds)
        self.setup_s = clock() - T_START
        self.log(f"set-up: {self.setup_s:.3f} s; {len(self.commits)} warm-up "
                 f"commits; counters (kernel rounds, overflows, reorganizes) "
                 f"{self.counters()}; compiles {self.setup_compiles[0]} in "
                 f"{self.setup_compiles[1]:.3f} s")

    def counters(self) -> tuple:
        d = self.driver
        return int(d.kernel_rounds), int(d.overflows), int(d.skiing.reorgs)

    def _commit(self, ids) -> load.Commit:
        c = load.commit(self.admin, load.insert_sql(self.tname, ids,
                                                    self.truth),
                        ids, clock(), self.counters, False)
        if c.error:
            raise RunError(f"warm-up commit failed: {c.error}")
        self.commits.append(c)
        return c

    def _warm_commits(self):
        """At least `commits` commits, until the update and reorganize
        programs have both run. A mix with readers also needs the probe
        and margin programs: its warm-up reads run after the first round
        that leaves rows inside some view's waters, the only state in
        which a read takes the margin step."""
        warm = self.traffic["warmup"]
        pool = iter(self.plan.warm_commits)
        base = self.counters()
        least = max(1, int(round(int(warm["commits"]) * self.warm_scale)))
        reads_due = bool(self.traffic.get("readers"))
        if reads_due:
            self.admin.prepare("pt", f"SELECT label FROM {self.vname} WHERE "
                                     f"id = ? AND view = ?")
        for i in range(int(warm["max_commits"])):
            kr, ov, rg = (a - b for a, b in zip(self.counters(), base))
            if i >= least and kr > ov and rg >= 1 and not reads_due:
                return
            self._commit(next(pool))
            if reads_due:
                band = self._band_pairs()
                if band.size:
                    self._warm_reads(band[:4])
                    reads_due = False
        raise RunError(f"warm-up not done within {warm['max_commits']} "
                       f"commits (counters {self.counters()})")

    def _band_pairs(self) -> np.ndarray:
        """(entity, view) pairs whose stored margin lies inside the view's
        waters: the reads the probe cannot answer (set-up only)."""
        st, d = self.facade.state, self.driver
        eps, gids = np.asarray(st.eps), np.asarray(st.gids)
        inside = reference.in_band(eps, d.lw[:, None].astype(np.float32),
                                   d.hw[:, None].astype(np.float32))
        inside &= gids[None, :] >= 0
        view, pos = np.nonzero(inside)
        return np.stack([gids[pos], view], axis=1)

    def _warm_reads(self, band: np.ndarray):
        hits = dict(self.facade.tier_hits)
        for entity, view in list(self.plan.warm_reads) + list(band):
            r = load.read(self.admin, int(entity), int(view), False)
            if r.error:
                raise RunError(f"warm-up read failed: {r.error}")
            self.reads.append(r)
        # every read runs the probe; a miss in some view adds the margin step
        disk = self.facade.tier_hits["disk"] - hits.get("disk", 0)
        if not disk:
            raise RunError("warm-up reads inside the waters did not run the "
                           "margin step")

    # -- the window -----------------------------------------------------
    def window(self):
        import jax
        wal = self.ex.metrics.histogram("span.wal.commit.seconds")
        snap = {}
        logdir = tempfile.mkdtemp(prefix="bench-trace-") if self.traced \
            else None
        marks = {}

        def on_open():
            snap["before"] = (wal.count, wal.sum, self.counters(),
                              dict(self.facade.tier_hits),
                              self.compiles.count)
            if logdir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # TraceAnnotations only
                jax.profiler.start_trace(logdir, profiler_options=opts)
                marks["t0"] = clock()

        def on_close():
            if logdir:
                marks["t1"] = clock()
                jax.profiler.stop_trace()

        pauses = GcPauses()
        gc.callbacks.append(pauses)
        try:
            win, commits, reads, hung = load.run_window(
                self.server.address, self.traffic, self.plan, self.tname,
                self.truth, self.vname, self.seconds, self.counters,
                self.traced, on_open, on_close)
            c0, s0, k0, t0, n0 = snap["before"]
            self.t0, self.t1 = win.t0, win.t1
            self.window_commits, self.window_reads = commits, reads
            self.hung = hung
            self.commits += [c for c in commits if c.error is None]
            self.reads += reads
            self.wal_delta = (wal.count - c0, wal.sum - s0)
            self.counter_delta = tuple(a - b for a, b in
                                       zip(self.counters(), k0))
            self.tier_delta = {t: self.facade.tier_hits[t] - t0.get(t, 0)
                               for t in self.facade.tier_hits}
            self.window_compiles = self.compiles.count - n0
            if logdir:
                ev = trace_reduce.load(logdir)
                self.trace = trace_reduce.reduce(ev, KERNEL)
                self.trace["window_s"] = marks["t1"] - marks["t0"]
        finally:
            gc.callbacks.remove(pauses)
            self.gc_pauses = pauses
            if logdir:
                shutil.rmtree(logdir, ignore_errors=True)

    # -- after the window -------------------------------------------------
    def memory_peak(self) -> int:
        """The peak bytes in use on the fullest of the cell's chips."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:int(self.cell["chips"])])

    def check(self):
        """The numbers compared with their limits, from the reference."""
        import jax
        st = self.facade.state
        gids = np.asarray(st.gids)
        labels = np.asarray(st.labels)
        eps = np.asarray(st.eps)
        W_s, b_s = np.asarray(st.W_stored), np.asarray(st.b_stored)
        W, b = np.asarray(self.facade.W), np.asarray(self.facade.b)
        history = list(self.ex.log.history)
        cfg = self.cfg
        k, vo = int(cfg["k"]), cfg["view_options"]
        groups = [c.ids for c in self.commits]
        # the epoch of every reorganize: the stored model is the model then
        prev = (0, 0, 0)
        last_reorg = 0
        for e, c in enumerate(self.commits, start=1):
            if c.counters[2] > prev[2]:
                last_reorg = e
            prev = c.counters
        read_epochs = {r.epoch for r in self.reads if r.error is None}
        keep = read_epochs | {last_reorg}
        if self.traced:                 # the need replays every round
            keep |= set(range(len(self.commits) + 1))
        t = clock()
        W_ref, b_ref, snaps = reference.sgd_replay(
            self.F, self.truth, groups, k, float(vo["lr"]), float(vo["l2"]),
            keep)
        replay_s = clock() - t
        if self.traced:
            self.launch_need_s = self._need(snaps)
        # the program's state is no longer needed on the device
        self.stop_server()
        self.facade.state = None
        del st
        gc.collect()
        jax.clear_caches()

        t = clock()
        checks = {}
        Ws_ref, bs_ref = snaps[last_reorg]
        checks["model_err"] = max(
            reference.model_error(W, b, W_ref, b_ref),
            reference.model_error(W_s, b_s, Ws_ref,
                                  np.asarray(bs_ref).astype(np.float32)))
        real = gids >= 0
        order = gids[real]
        if not np.array_equal(np.sort(order), np.arange(self.n)):
            raise RunError("the device table does not hold each entity once")
        lab = np.empty((k, self.n), np.int8)
        lab[:, order] = labels[:, real]
        Z = reference.margins(self.F, W_ref, b_ref)
        checks["label_mismatch"], near = reference.label_mismatches(lab, Z)
        Zs = reference.margins(self.F, Ws_ref, bs_ref)
        checks["eps_err"] = float(np.max(np.abs(eps[:, real]
                                                - Zs[order].T)))
        answers = np.array([(r.entity, r.view, r.label, r.epoch)
                            for r in self.reads if r.error is None],
                           np.int64).reshape(-1, 4)
        checks["answer_mismatch"], near_ans = reference.answer_mismatches(
            self.F, answers, snaps)
        checks["wal_mismatch"] = self._wal_mismatch(history)
        checks["ack_bad"] = sum(
            1 for e, c in enumerate(self.commits, start=1)
            if c.epoch != e or c.ack != [[len(c.ids), 1]])
        checks["hung_sessions"] = self.hung
        self.log(f"reference: replayed {len(groups)} commits in "
                 f"{replay_s:.3f} s, checked in {clock() - t:.3f} s; "
                 f"{near} (row, view) pairs and {near_ans} of {len(answers)} "
                 f"answers within |z| <= {reference.TOL:g}")
        return checks

    def _wal_mismatch(self, history) -> int:
        """Committed WAL groups that differ from what the sessions sent
        and saw acknowledged, in epoch order (exact)."""
        groups, cur = [], []
        for rec in history:
            if rec.op == "commit":
                groups.append(cur)
                cur = []
            else:
                cur.append((rec.op, rec.entity_id, int(rec.label)))
        sent = [[("insert", int(i), int(self.truth[i])) for i in c.ids]
                for c in self.commits]
        bad = abs(len(groups) - len(sent)) + int(bool(cur))
        return bad + sum(g != s for g, s in zip(groups, sent))

    def _need(self, snaps):
        """Seconds each band-kernel launch in the window needs at the peak
        bandwidth (`need.py`): the union over views of the Lemma 3.1 band,
        from the stored margins held before the round and that round's
        Eq. 2 waters, both worked out here from the reference's models."""
        import jax.numpy as jnp
        vo = self.cfg["view_options"]
        k = int(self.cfg["k"])
        M = reference.row_norm_max(self.F, float(vo["q"]))
        st = self.facade.state
        real = st.gids >= 0
        lw = hw = np.zeros(k)
        r, prev, out = 0, (0, 0, 0), []
        for e, c in enumerate(self.commits, start=1):
            kr, ov, rg = (a - p for a, p in zip(c.counters, prev))
            prev = c.counters
            if kr:
                W_r, b_r = snaps[r]
                lw, hw = reference.waters(lw, hw, *snaps[e], W_r, b_r, M,
                                          float(vo["p"]))
                if c.sent >= self.t0:
                    rows = int(need.union_band_rows(
                        st.F, real, jnp.asarray(W_r),
                        jnp.asarray(b_r, jnp.float32),
                        jnp.asarray(lw, jnp.float32),
                        jnp.asarray(hw, jnp.float32)))
                    out.append(need.need_seconds(rows, self.d, k, self.peaks))
            if rg:
                r, lw, hw = e, np.zeros(k), np.zeros(k)
        return out

    # -- the result -------------------------------------------------------
    def wire_share(self, items):
        """1 - server statement time / client time, over answered items."""
        ok = [x for x in items if x.error is None and x.server_us is not None]
        client = sum(x.done - x.sent for x in ok)
        if not ok or client <= 0:
            return None
        return 100.0 * (1.0 - sum(x.server_us for x in ok) / 1e6 / client)

    def device_idle(self):
        t = self.trace
        if not t or t["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

    def stop_server(self):
        if getattr(self, "admin", None) is not None:
            self.admin.close()
            self.admin = None
        if getattr(self, "server", None) is not None:
            self.server.stop()
            self.server = None

    def close(self):
        import jax
        self.stop_server()
        if getattr(self, "compiles", None) is not None:
            jax.monitoring.unregister_event_duration_listener(self.compiles)

    def in_window(self, items, weight=lambda x: 1) -> float:
        """Work done inside the window: each answered item counts with the
        share of its send-to-reply time that lies inside [t0, t1)."""
        total = 0.0
        for x in items:
            if x.error is None and x.done > x.sent:
                inside = min(x.done, self.t1) - max(x.sent, self.t0)
                total += weight(x) * min(1.0, max(0.0, inside
                                                  / (x.done - x.sent)))
        return total

    def end_to_end(self, name: str) -> float:
        s = self.seconds
        commits = [c for c in self.window_commits if c.error is None]
        reads = [r for r in self.window_reads if r.error is None]
        if name == "setup_s":
            return self.setup_s
        if name == "train_rows_per_s":
            return self.in_window(commits, lambda c: len(c.ids)) / s
        if name == "commit_p95_ms":
            return p95([(c.done - c.due) * 1e3 for c in commits])
        if name == "read_p95_ms":
            return p95([(r.done - r.sent) * 1e3 for r in reads])
        if name == "reads_per_s":
            return self.in_window(reads) / s
        raise RunError(f"no end-to-end metric {name!r}")

    def describe_window(self):
        commits = [c for c in self.window_commits if c.error is None]
        reads = [r for r in self.window_reads if r.error is None]
        if commits:
            lat = [(c.done - c.due) * 1e3 for c in commits]
            late = [(c.sent - c.due) * 1e3 for c in commits]
            half = len(lat) // 2
            self.log(f"commits: {len(commits)} in the window, latency median "
                     f"{np.median(lat):.3f} ms (first half {np.median(lat[:half]):.3f}, "
                     f"second half {np.median(lat[half:]):.3f}), p95 "
                     f"{p95(lat):.3f} ms, max {max(lat):.3f} ms at commit "
                     f"{int(np.argmax(lat))}; writer lateness median "
                     f"{np.median(late):.3f} ms, max {max(late):.3f} ms")
        if reads:
            lat = [(r.done - r.sent) * 1e3 for r in reads]
            self.log(f"reads: {len(reads)} in the window, latency median "
                     f"{np.median(lat):.3f} ms, p95 {p95(lat):.3f} ms, max "
                     f"{max(lat):.3f} ms")
        self.log(f"window: counters delta (kernel rounds, overflows, "
                 f"reorganizes) {self.counter_delta}, tier hits "
                 f"{self.tier_delta}, wal commits {self.wal_delta[0]}, "
                 f"compiles in the window {self.window_compiles}; Python "
                 f"collections by generation {self.gc_pauses.count}, seconds "
                 f"{[round(s, 4) for s in self.gc_pauses.seconds]}")


def run_cell(argv: Optional[Sequence[str]] = None, *,
             rehearsal: Optional[dict] = None, root: Path = ROOT,
             out=sys.stdout, err=sys.stderr) -> int:
    """One run. The cell, its configuration, mix and limits are read from
    the checkout at `root`. `rehearsal` (tests only; a configuration's
    `rehearsal` object) runs on the CPU at a tiny size:
    {"rows": ..., "warm_scale": ..., "table": {key: value, ...}}."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=out, flush=True)

    def fail(msg, code):
        print(f"run.py: {msg}", file=err, flush=True)
        return code

    try:
        manifest, cell, cfg, traffic, limits = load_cell(args.workload, root)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e}", 2)
    if rehearsal:
        cfg["table"].update(rehearsal.get("table", {}))
    sys.path.insert(0, str(root / "src"))
    try:
        import jax
        import repro.rdbms  # noqa: F401
    except ImportError as e:
        return fail(f"the system under test is not importable: {e}", 2)
    devices = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devices[0].platform != want or len(devices) < int(cell["chips"]):
        return fail(f"this cell needs {cell['chips']} {want} chip(s); JAX "
                    f"found {len(devices)} {devices[0].platform} device(s)", 2)
    peaks_all = json.loads((HERE / "peaks.json").read_text())
    kind = devices[0].device_kind
    if not rehearsal and kind not in peaks_all:
        return fail(f"no peaks for device kind {kind!r} in peaks.json", 2)
    peaks = peaks_all.get(kind, peaks_all["TPU v5 lite"])
    log(f"device: {devices[0].platform} {kind} x{len(devices)}; jax "
        f"{jax.__version__}; cell {cell['name']}, seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}")
    run = Run(cell, cfg, traffic, args.seed, args.seconds,
              bool(args.trace), peaks,
              rows=(rehearsal or {}).get("rows"),
              warm_scale=(rehearsal or {}).get("warm_scale", 1.0), log=log)
    if traffic.get("writer") and traffic["writer"]["loop"] == "open" \
            and not traffic["writer"].get("commits_per_s"):
        return fail("the open-loop writer has no commits_per_s", 2)
    try:
        run.setup()
        run.window()
        peak = run.memory_peak()
        run.describe_window()
        checks = run.check()
    except RunError as e:
        return fail(f"FAILED: {e}", 1)
    finally:
        run.close()

    names = reported(manifest["per_layer"] if run.traced
                     else manifest["end_to_end"], cell["name"])
    metrics = {}
    for m in names:
        if run.traced:
            v = module_from(HERE / "layers" / f"{m['name']}.py").read(run)
        else:
            v = run.end_to_end(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(run.window_commits) + len(run.window_reads)
    failed = sum(1 for x in run.window_commits + run.window_reads
                 if x.error is not None)
    compared = {}
    for name, value in checks.items():
        limit = 0 if name in EXACT_CHECKS else float(limits[name])
        compared[name] = {"value": value, "limit": limit}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in compared.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run_cell())
