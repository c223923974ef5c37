"""The one traffic generator: every mix is a file under `traffic/` that this
module reads.

A mix file gives `rows_per_commit`, a `warmup` block, and optionally a
`writer` (closed loop, or open loop at `commits_per_s`) and `readers`
(closed-loop sessions of the prepared point read). Everything a run sends
is drawn from `--seed` before the window opens (`make_plan`), so the same
seed sends the same statements; a closed loop only decides how far into
its pool a run gets.

Sessions are `SqlClient` connections driven from threads of the benchmark
process. Each statement is timed on the host clock from when it was due
(open loop) or sent (closed loop) to its reply, and wrapped in a profiler
`TraceAnnotation` ("commit" or "read") when the run is traced, so the trace
reduction can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import socket
import threading
import time
from typing import Callable, List, Optional

import numpy as np

clock = time.perf_counter
WINDOW_COMMITS = 20_000      # closed-loop writer pool (more than a run sends)
READS_PER_SESSION = 200_000  # closed-loop reader pool per session
JOIN_GRACE_S = 60.0          # how long past the window a reply may come


@dataclasses.dataclass
class Plan:
    """All statements of one run, drawn from the seed."""
    warm_commits: List[np.ndarray]           # (rows_per_commit,) ids each
    window_commits: List[np.ndarray]
    warm_reads: np.ndarray                   # (m, 2) id, view
    reads: List[np.ndarray]                  # per session (R, 2) id, view


def zipfian(rng: np.random.Generator, n: int, theta: float, size: int,
            scramble: np.ndarray) -> np.ndarray:
    """YCSB's scrambled Zipfian: rank r has weight 1 / r**theta, and the
    ranks are spread over the ids by a fixed permutation."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta)
    cdf /= cdf[-1]
    ranks = np.minimum(np.searchsorted(cdf, rng.random(size)), n - 1)
    return scramble[ranks]


def make_plan(traffic: dict, n: int, k: int, rng: np.random.Generator,
              *, window_commits: int = WINDOW_COMMITS,
              reads_per_session: int = READS_PER_SESSION) -> Plan:
    g = int(traffic["rows_per_commit"])
    warm = traffic["warmup"]
    n_warm = int(warm["max_commits"])
    commits = rng.integers(0, n, size=(n_warm + window_commits, g),
                           dtype=np.int64)
    rd = traffic.get("readers")
    sessions = int(rd["sessions"]) if rd else 0
    per = reads_per_session if sessions else 0
    m = int(warm.get("reads", 0))
    total = sessions * per + m
    if rd and rd["ids"] == "zipfian":
        ids = zipfian(rng, n, float(rd["zipf_constant"]), total,
                      rng.permutation(n))
    else:
        ids = rng.integers(0, n, size=total)
    views = rng.integers(0, k, size=total)
    pairs = np.stack([ids, views], axis=1).astype(np.int64)
    return Plan(warm_commits=list(commits[:n_warm]),
                window_commits=list(commits[n_warm:]),
                warm_reads=pairs[:m],
                reads=[pairs[m + s * per: m + (s + 1) * per]
                       for s in range(sessions)])


def insert_sql(table: str, ids: np.ndarray, truth: np.ndarray) -> str:
    rows = ", ".join(f"({int(i)}, {int(truth[i])})" for i in ids)
    return f"INSERT INTO {table} (id, class) VALUES {rows}"


@dataclasses.dataclass
class Commit:
    due: float
    sent: float
    done: float
    ids: np.ndarray
    epoch: Optional[int]
    ack: Optional[list]          # the reply's rows ([[queued, commits]])
    server_us: Optional[float]
    counters: tuple              # driver (kernel_rounds, overflows, reorgs)
    error: Optional[str] = None


@dataclasses.dataclass
class Read:
    sent: float
    done: float
    entity: int
    view: int
    label: Optional[int]
    epoch: Optional[int]
    server_us: Optional[float]
    error: Optional[str] = None


class Window:
    """The measured interval, shared by every session thread."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.go = threading.Event()
        self.t0 = self.t1 = 0.0

    def open(self) -> None:
        self.t0 = clock()
        self.t1 = self.t0 + self.seconds
        self.go.set()


def _annotate(trace: bool, name: str):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


def commit(client, sql: str, ids, due: float, counters: Callable[[], tuple],
           trace: bool) -> Commit:
    from repro.rdbms.client import ServerError, WireError
    sent = clock()
    try:
        with _annotate(trace, "commit"):
            res = client.run_one(sql)
        done = clock()
        return Commit(due, sent, done, ids, res.epoch, res.rows,
                      res.elapsed_us, counters())
    except (ServerError, WireError, OSError) as e:
        return Commit(due, sent, clock(), ids, None, None, None, counters(),
                      error=f"{type(e).__name__}: {e}")


def read(client, entity: int, view: int, trace: bool) -> Read:
    from repro.rdbms.client import ServerError, WireError
    sent = clock()
    try:
        with _annotate(trace, "read"):
            res = client.run_prepared("pt", [entity, view])
        done = clock()
        return Read(sent, done, entity, view, int(res.rows[0][0]), res.epoch,
                    res.elapsed_us)
    except (ServerError, WireError, OSError, IndexError) as e:
        return Read(sent, clock(), entity, view, None, None, None,
                    error=f"{type(e).__name__}: {e}")


def writer_session(client, spec: dict, pool: List[np.ndarray], table: str,
                   truth: np.ndarray, window: Window, counters, trace: bool,
                   out: List[Commit]) -> None:
    """Closed loop: the next commit is sent when the last one returns.
    Open loop: commit i is due at t0 + i / commits_per_s and is sent then,
    or at once when the writer is behind; every commit due inside the
    window is sent, and its latency counts from when it was due."""
    window.go.wait()
    rate = spec.get("commits_per_s")
    for i, ids in enumerate(pool):
        if spec["loop"] == "open":
            due = window.t0 + i / float(rate)
            if due >= window.t1:
                break
            wait = due - clock()
            if wait > 0:
                time.sleep(wait)
        else:
            due = clock()
            if due >= window.t1:
                break
        c = commit(client, insert_sql(table, ids, truth), ids, due, counters,
                   trace)
        out.append(c)
        if c.error and not c.error.startswith("ServerError"):
            break             # the session's connection is gone


def reader_session(client, pairs: np.ndarray, window: Window, trace: bool,
                   out: List[Read]) -> None:
    window.go.wait()
    for entity, view in pairs:
        if clock() >= window.t1:
            break
        r = read(client, int(entity), int(view), trace)
        out.append(r)
        if r.error and not r.error.startswith("ServerError"):
            break             # the session's connection is gone


def run_window(address, traffic: dict, plan: Plan, table: str,
               truth: np.ndarray, view: str, seconds: float, counters,
               trace: bool, on_open: Callable[[], None] = lambda: None,
               on_close: Callable[[], None] = lambda: None):
    """Open every session, run the window, and wait for every reply.
    Returns (window, commits, reads, sessions still running)."""
    from repro.rdbms.client import SqlClient
    window = Window(seconds)
    commits: List[Commit] = []
    reads: List[List[Read]] = []
    clients, threads = [], []
    try:
        spec = traffic.get("writer")
        if spec:
            c = SqlClient.connect(*address, timeout=JOIN_GRACE_S + seconds)
            clients.append(c)
            threads.append(threading.Thread(
                target=writer_session, name="bench-writer",
                args=(c, spec, plan.window_commits, table, truth, window,
                      counters, trace, commits)))
        for s, pairs in enumerate(plan.reads):
            c = SqlClient.connect(*address, timeout=JOIN_GRACE_S + seconds)
            clients.append(c)
            c.prepare("pt", f"SELECT label FROM {view} WHERE id = ? "
                            f"AND view = ?")
            reads.append([])
            threads.append(threading.Thread(
                target=reader_session, name=f"bench-reader-{s}",
                args=(c, pairs, window, trace, reads[-1])))
        for t in threads:
            t.start()
        on_open()
        window.open()
        time.sleep(max(0.0, window.t1 - clock()))
        on_close()
        for t in threads:
            t.join(max(0.0, window.t1 + JOIN_GRACE_S - clock()))
    finally:
        window.go.set()       # a failed start leaves t1 = 0: sessions end
        hung = sum(t.is_alive() for t in threads)
        for c in clients:
            if hung:          # unblock a session stuck on its socket
                c._sock.shutdown(socket.SHUT_RDWR)
            c.close()
        for t in threads:
            t.join(5.0)
    return window, commits, [r for rs in reads for r in rs], hung
