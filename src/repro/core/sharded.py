"""Row-sharded HAZY view maintenance on the device (jit/shard_map twin of
multiview.py).

Stateful shell #3 over the functional core in `core/engine.py`: every
algorithm rule — the Lemma 3.1 partition (`covering_windows`/`band_mask`
inside the update step, `probe_partition` inside the hybrid probe), the
Eq. 2 waters update (host-side in the driver, via `waters_update`) and the
SKIING charge rule (via `Skiing`) — is imported from engine.py; this
module owns the sharding layout, the shard_map plumbing and the kernel
launch.

Layout: k one-vs-all views (k = 1 is one case) share ONE scratch table,
its rows sharded over ("pod", "data") and replicated over "model", and
kept in a shard-local SHARED clustering order (sorted by min_v |eps_v|,
the distance to the nearest view's decision boundary, so every view's band
is clustered near the front of the shard). The order is maintained
entirely device-side: the reorganize step re-sorts it, with no collective
(the clustering is shard-local); the update step computes per-view
covering windows of the Lemma 3.1 band in that order
(`engine.covering_windows`) and relabels the union of the k windows with
ONE `multiview_band_reclassify` Pallas launch, which streams each tile of
that union once for all k views — no vmapped per-view dynamic slices, and
no capacity: a window may span the whole shard, so SKIING alone decides
when to reorganize. The kernel computes sign(w_v·f − b_v) from whole
feature rows, hence the model-replicated rows (the (k, d) models are
tiny). The §3.5.2 hybrid read rides the same state:
`make_multiview_hybrid_probe_step` looks the entity up in the eps-map,
resolves what the waters can, and labels the views they cannot from the
entity's one feature row, all in one program.

Observation: every jitted program is named for what it does (`PROGRAMS`),
so the profiler's "XLA Modules" line and JAX's compile events say which
one ran or recompiled. A driver counts the compiles of those programs in
its metrics registry and mirrors every `repro.obs` span into the
profiler's host trace (`observe`). The driver opens `round.*` spans inside
a maintenance round and `read.*` spans inside a point read.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (classify, covering_windows, probe_partition,
                               waters_update)
from repro.kernels.band_reclassify.ops import multiview_band_reclassify
from repro.obs import trace

# f32 margins on every backend: XLA's TPU default for f32 dots is one bf16
# pass, which would make the stored eps (and so the Lemma 3.1 band) only
# bf16-accurate
HIGHEST = jax.lax.Precision.HIGHEST


def _row_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def row_shards(mesh: Mesh) -> int:
    """How many row shards the table is cut into on `mesh`."""
    return int(np.prod([mesh.shape[a] for a in _row_axes(mesh)]))


# ---------------------------------------------------------------------------
# Observation: named programs, their compiles, and the span mirror
# ---------------------------------------------------------------------------

# the driver's jitted programs, by the name each step function carries: the
# trace's "XLA Modules" line shows `jit_<name>`, compile events `jit(<name>)`
PROGRAMS = ("band_update", "reorganize", "probe", "all_members")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_watching: "weakref.WeakSet" = weakref.WeakSet()   # registries counting
_listening = False                                  # compiles


def _count_compile(event: str, duration: float, **kw: Any) -> None:
    if event != _COMPILE_EVENT:
        return
    name = str(kw.get("fun_name", ""))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    if name not in PROGRAMS:
        return
    for reg in list(_watching):
        reg.counter(f"compiles.{name}").inc()
        reg.histogram(f"compiles.{name}.seconds").observe(duration)


def _profiler_annotation(name: str):
    """The span's host annotation while a profile is being collected, else
    None (a span then costs one check more)."""
    ann = jax.profiler.TraceAnnotation
    return ann(name) if ann.is_enabled() else None


def observe(metrics: Optional[Any] = None) -> None:
    """Mirror every `repro.obs` span into the profiler's host trace, and
    count this process's compiles of the named `PROGRAMS` in `metrics`:
    counters `compiles.<program>` and histograms
    `compiles.<program>.seconds` (a persistent-cache hit counts too)."""
    global _listening
    trace.set_mirror(_profiler_annotation)
    if metrics is None:
        return
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        _listening = True
    _watching.add(metrics)


# ---------------------------------------------------------------------------
# Steps (built per mesh; call under `with mesh:` or pass to jit/lower) and
# the host driver: k one-vs-all views over ONE shared scratch table kept in
# a device-resident SHARED clustering order (sorted by min_v |eps_v|). The
# update step relabels the k covering windows with ONE Pallas kernel launch.
# ---------------------------------------------------------------------------

class ShardedMultiViewState(NamedTuple):
    """k views sharing one scratch table in a shared clustering order.

    The shared order is the device-resident form of the engine's clustering
    permutation: each shard keeps its local rows sorted by min_v |eps_v|
    (distance to the nearest view's decision boundary), so every view's
    Lemma 3.1 band is a small covering window near the front of the shard —
    the exact window form `multiview_band_reclassify` consumes. `gids` IS
    the perm (position -> global entity id); reorganization re-sorts rows,
    eps and labels together, entirely on device. F rows are kept whole
    (row-sharded, model-replicated) because the band kernel computes
    sign(w_v·f − b_v) per row.

    Each shard holds a whole number of kernel tiles, so the table is padded
    to n_pad rows. A padding row has gid `PAD_GID`, zero features, eps +inf
    (never inside a band, sorted to the back of its shard) and label 0 (never
    counted as a member of either side)."""
    F: jax.Array            # (n_pad, d) f32 — scratch rows, shared order
    gids: jax.Array         # (n_pad,) i32 global entity id per scratch row
    eps: jax.Array          # (k, n_pad) f32 stored-model margins, shared order
    labels: jax.Array       # (k, n_pad) int8 aligned to the shared order
    W_stored: jax.Array     # (k, d) f32 (replicated)
    b_stored: jax.Array     # (k,) f32
    lw: jax.Array           # (k,) f32
    hw: jax.Array           # (k,) f32


PAD_GID = -1                # gid of a padding row of the scratch table
# bytes of one f32 (block_n, d) tile of F in VMEM; the kernel pipeline holds
# two of them, which stays inside the smallest default scoped-VMEM limit
VMEM_TILE_BYTES = 2 << 20
# `band.window_rows` buckets: 128 rows (one tile) .. 2**26 rows
WINDOW_ROW_BUCKETS = tuple(float(2 ** e) for e in range(7, 27))


def multiview_state_specs(n_pad: int, d: int, k: int, mesh: Mesh,
                          dtype=jnp.float32):
    row_axes = _row_axes(mesh)
    rows = P(row_axes)
    krows = P(None, row_axes)

    def sds(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))

    return ShardedMultiViewState(
        F=sds((n_pad, d), dtype, P(row_axes, None)),   # model-replicated rows
        gids=sds((n_pad,), jnp.int32, rows),
        eps=sds((k, n_pad), jnp.float32, krows),
        labels=sds((k, n_pad), jnp.int8, krows),
        W_stored=sds((k, d), jnp.float32, P()),
        b_stored=sds((k,), jnp.float32, P()),
        lw=sds((k,), jnp.float32, P()),
        hw=sds((k,), jnp.float32, P()),
    )


def _mv_specs(mesh: Mesh):
    rows = _row_axes(mesh)
    return (P(rows, None), P(rows), P(None, rows))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def mv_tiles(mesh: Mesh, n: int, d: int):
    """(n_pad, block_n, n_local) for the band kernel.

    block_n is a multiple of 128 (the TPU lane tile of the (1, block_n)
    label blocks) sized so that one f32 (block_n, d) tile of F, lanes padded
    to 128, fits `VMEM_TILE_BYTES`, and no larger than a shard needs. Each
    row shard holds n_local rows: its share of the n real rows padded up to
    a multiple of block_n, so the table holds n_pad = n_local * shards rows
    (the padding sits at the end)."""
    n_shards = row_shards(mesh)
    per_shard = -(-n // n_shards)
    block_n = VMEM_TILE_BYTES // (4 * _round_up(d, 128)) // 128 * 128
    block_n = min(max(128, block_n), _round_up(per_shard, 128))
    n_local = _round_up(per_shard, block_n)
    return n_local * n_shards, block_n, n_local


def kernel_interpret(mesh: Mesh) -> bool:
    """The band kernel is interpreted only on a CPU mesh: a TPU mesh always
    gets the compiled kernel, and any other platform is an error."""
    platform = mesh.devices.flat[0].platform
    if platform not in ("cpu", "tpu"):
        raise ValueError(f"the band kernel runs on TPU (or interpreted on "
                         f"CPU); this mesh is on {platform!r}")
    return platform == "cpu"


def make_multiview_update_step(mesh: Mesh, block_n: int):
    """Banded incremental step for all k views in ONE Pallas launch.

    Per shard: `engine.covering_windows` locates each view's covering
    window of the Lemma 3.1 band in the shared order (pure device compute,
    no per-view dynamic slices), then `multiview_band_reclassify` streams
    the union of the k windows HBM->VMEM once and relabels each view's
    window under the stacked models. Returns (labels', counts (k + shards,)
    i32): the true band width of each view summed over shards, then the
    rows each shard's kernel streamed, in shard order — one array from one
    psum, so one collective and one host copy."""
    pf, pr, pkr = _mv_specs(mesh)
    rows = _row_axes(mesh)
    shards = row_shards(mesh)
    interpret = kernel_interpret(mesh)

    def local(F, gids, eps, labels, W_s, b_s, lw, hw, W, b):
        start, end, width = covering_windows(eps, lw, hw, xp=jnp)
        labels, streamed = multiview_band_reclassify(
            F, labels, W, b, start, end, block_n=block_n,
            interpret=interpret)
        shard = 0                 # this shard's place in the row order
        for ax in rows:
            shard = shard * mesh.shape[ax] + jax.lax.axis_index(ax)
        mine = jnp.zeros(shards, jnp.int32).at[shard].set(
            streamed.astype(jnp.int32))
        counts = jnp.concatenate([width, mine])
        for ax in rows:
            counts = jax.lax.psum(counts, ax)
        return labels, counts

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pf, pr, pkr, pkr, P(), P(), P(), P(), P(), P()),
        out_specs=(pkr, P()),
        check_vma=False)     # pallas_call outputs carry no varying-axes type

    def band_update(state: ShardedMultiViewState, W, b):
        # only the new labels leave the step: returning the whole state
        # would make the jitted program copy F (the table) on every round
        return fn(*state, W, b)

    return band_update


def make_multiview_reorganize_step(mesh: Mesh):
    """Re-sort the SHARED clustering order from one `F @ W.T` product: the
    new order sorts shard-local rows by min_v |eps_v| so that every view's
    band clusters near the front of the shard, and padding rows (eps +inf)
    to its back. Rows, gids, eps and labels move together; no collectives
    at all (shard-local clustering, and F rows are whole so there is no
    model-axis psum either)."""
    pf, pr, pkr = _mv_specs(mesh)

    def local(F, gids, eps, labels, W_s, b_s, lw, hw, W, b):
        Z = jnp.einsum("nd,kd->kn", F.astype(jnp.float32), W,
                       precision=HIGHEST) - b[:, None]
        Z = jnp.where(gids[None, :] == PAD_GID, jnp.inf, Z)
        key = jnp.min(jnp.abs(Z), axis=0)          # nearest-boundary distance
        # the same stable argsort over a (1, n) row: the TPU compiler takes
        # half as long over it as over the 1-D array (~45 s vs ~95 s at
        # 721,408 rows)
        order = jnp.argsort(key[None, :], axis=1)[0].astype(jnp.int32)
        # `order` is a permutation, so every index is in bounds: take's
        # default fill mode would only add a select over each gathered
        # array, which the TPU compiler does not fuse into the gather (a
        # second read and write of the whole table)
        F_new = jnp.take(F, order, axis=0, mode="clip")
        gids_new = jnp.take(gids, order, mode="clip")
        eps_new = jnp.take(Z, order, axis=1, mode="clip")
        labels_new = jnp.where(gids_new[None, :] == PAD_GID, jnp.int8(0),
                               classify(eps_new, xp=jnp))
        return F_new, gids_new, eps_new, labels_new

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pf, pr, pkr, pkr, P(), P(), P(), P(), P(), P()),
        out_specs=(pf, pr, pkr, pkr))

    def reorganize(state: ShardedMultiViewState, W, b):
        F, gids, eps, labels = fn(*state, W, b)
        zeros = jnp.zeros(b.shape, jnp.float32)
        return ShardedMultiViewState(F, gids, eps, labels, W, b, zeros, zeros)

    return reorganize


def make_multiview_hybrid_probe_step(mesh: Mesh):
    """§3.5.2 hybrid read of ONE entity across all k views, in one program:
    the entity's stored eps per view comes from the eps-map (masked
    row-shard sum over the shared `gids`, psum'd), and the waters test is
    THE shared Lemma 3.1 point-probe (engine.probe_partition); the views
    it leaves unresolved take their label from the entity's margins under
    the current models (W, b), computed from its one feature row (found
    by its position in the shard's `gids`). The row is gathered whatever
    the waters say: it is one row, and a second program would cost a
    dispatch and a host copy. Returns (2, k) int8: the labels, then 1
    where the waters resolved the view — one array, so one host copy."""
    pf, pr, pkr = _mv_specs(mesh)
    rows = _row_axes(mesh)

    def local(F, gids, eps, labels, W_s, b_s, lw, hw, W, b, eid):
        hit = gids == eid                    # entity appears once globally
        e = jnp.sum(jnp.where(hit[None, :], eps, 0.0), axis=1)
        row = jax.lax.dynamic_index_in_dim(F, jnp.argmax(hit), 0,
                                           keepdims=False)
        f = jnp.where(jnp.any(hit), row.astype(jnp.float32), 0.0)
        z = jnp.einsum("kd,d->k", W, f, precision=HIGHEST)
        for ax in rows:            # other row shards contribute exact zeros
            e = jax.lax.psum(e, ax)
            z = jax.lax.psum(z, ax)
        probed = probe_partition(e, lw, hw, xp=jnp)     # 0 = unresolved
        resolved = probed != 0
        lab = jnp.where(resolved, probed, classify(z - b, xp=jnp))
        return jnp.stack([lab, resolved.astype(jnp.int8)])

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pf, pr, pkr, pkr, P(), P(), P(), P(), P(), P(), P()),
        out_specs=P())

    def probe(state: ShardedMultiViewState, W, b, entity_id):
        return fn(*state, W, b, entity_id)

    return probe


def make_multiview_all_members_step(mesh: Mesh):
    _, _, pkr = _mv_specs(mesh)
    rows = _row_axes(mesh)

    def local(labels):
        c = jnp.sum((labels == 1).astype(jnp.int32), axis=1)
        for ax in rows:
            c = jax.lax.psum(c, ax)
        return c

    fn = jax.shard_map(local, mesh=mesh, in_specs=(pkr,), out_specs=P())

    def all_members(state):
        return fn(state.labels)

    return all_members


@dataclasses.dataclass
class ShardedMultiViewHazy:
    """Host driver for k views: pooled SKIING (a reorganization re-sorts the
    one shared order for all views, so the strategy treats it as one global
    op), per-view Hölder waters kept host-side via `engine.waters_update`.
    `apply_models` reclassifies the union band through the
    `multiview_band_reclassify` kernel against the device-resident shared
    clustering order; SKIING alone decides when to reorganize. `n` counts
    real entity rows; the device table holds `n_pad` rows, `cap` of them
    on each of its `shards` row shards — the widest window the kernel
    takes. What a round sends to the device (models, waters, a read's
    entity id) is put replicated on the mesh. In its metrics registry,
    once a launch, the histogram `band.window_rows` takes the rows the
    kernel streamed summed over shards, and `band.shard_window_rows_max`
    the widest shard's; a launch inside a profiled window also feeds
    their `profiled.` twins."""
    mesh: Mesh
    n: int
    d: int
    k: int
    M: float
    p: float = 2.0
    alpha: float = 1.0
    metrics: Optional[Any] = None     # registry for spans and compiles

    def __post_init__(self):
        observe(self.metrics)
        self.shards = row_shards(self.mesh)
        self.n_pad, self.block_n, self.cap = mv_tiles(
            self.mesh, self.n, self.d)
        self._replicated = NamedSharding(self.mesh, P())
        self._update = jax.jit(
            make_multiview_update_step(self.mesh, self.block_n))
        self._reorg = jax.jit(make_multiview_reorganize_step(self.mesh))
        self._count = jax.jit(make_multiview_all_members_step(self.mesh))
        self._probe = jax.jit(make_multiview_hybrid_probe_step(self.mesh))
        from repro.core.skiing import Skiing
        self.skiing = Skiing(S=1.0, alpha=self.alpha)
        self.lw = np.zeros(self.k, np.float64)
        self.hw = np.zeros(self.k, np.float64)
        # the last round's (W, b) on the mesh (from `init_state` on): point
        # reads label under them without copying the models again
        self.models = None
        self.kernel_rounds = 0    # update-step launches of the band kernel
        self.overflows = 0        # always 0: no kernel capacity to overflow

    def _replicate(self, x, dtype):
        """A host value on every device of the mesh."""
        return jax.device_put(np.asarray(x, dtype), self._replicated)

    def _put_models(self, W, b):
        return (self._replicate(W, np.float32), self._replicate(b, np.float32))

    def put_table(self, F: np.ndarray) -> ShardedMultiViewState:
        """The state before its first reorganize: each row shard of F is
        put from the host table on its own device, and only the last
        shard's padding rows are built on the host, so no padded copy of
        the whole table is made. Span: `view.put_table`, which waits for
        the table to reach the devices."""
        k, n, n_pad = self.k, self.n, self.n_pad
        specs = multiview_state_specs(n_pad, self.d, k, self.mesh)
        put = lambda x, s: jax.device_put(x, s.sharding)
        F = np.asarray(F, np.float32)

        def rows(index):          # the rows of one shard, zeros past n
            lo, hi, _ = index[0].indices(n_pad)
            if hi <= n:
                return F[lo:hi]
            out = np.zeros((hi - lo, self.d), np.float32)
            out[:max(0, n - lo)] = F[lo:n]
            return out

        gids = np.full(n_pad, PAD_GID, np.int32)
        gids[:n] = np.arange(n, dtype=np.int32)
        with trace.span("view.put_table", metrics=self.metrics):
            F_dev = jax.make_array_from_callback(
                (n_pad, self.d), specs.F.sharding, rows)
            F_dev.block_until_ready()
        return ShardedMultiViewState(
            F=F_dev,
            gids=put(gids, specs.gids),
            eps=put(np.zeros((k, n_pad), np.float32), specs.eps),
            labels=put(np.zeros((k, n_pad), np.int8), specs.labels),
            W_stored=put(np.zeros((k, self.d), np.float32), specs.W_stored),
            b_stored=put(np.zeros(k, np.float32), specs.b_stored),
            lw=put(np.zeros(k, np.float32), specs.lw),
            hw=put(np.zeros(k, np.float32), specs.hw),
        )

    def init_state(self, F: np.ndarray) -> ShardedMultiViewState:
        self.models = self._put_models(np.zeros((self.k, self.d)),
                                       np.zeros(self.k))
        return self._reorg(self.put_table(F), *self.models)

    def _do_reorg(self, state, W, b):
        with trace.span("round.reorganize", metrics=self.metrics):
            self.models = self._put_models(W, b)
            state = self._reorg(state, *self.models)
        self.skiing.record_reorg()
        self.lw[:] = 0.0
        self.hw[:] = 0.0
        return state

    def _observe_window(self, shard_rows: np.ndarray) -> None:
        """One launch's streamed rows (one entry a shard) into
        `band.window_rows` (their sum) and `band.shard_window_rows_max`
        (the widest shard's), and into their `profiled.` twins while a
        profile is collected."""
        m = self.metrics
        if m is None:
            return
        seen = {"band.window_rows": float(np.sum(shard_rows)),
                "band.shard_window_rows_max": float(np.max(shard_rows))}
        profiled = jax.profiler.TraceAnnotation.is_enabled()
        for name, rows in seen.items():
            m.histogram(name, WINDOW_ROW_BUCKETS).observe(rows)
            if profiled:
                m.histogram(f"profiled.{name}",
                            WINDOW_ROW_BUCKETS).observe(rows)

    def apply_models(self, state: ShardedMultiViewState, W, b):
        """One eager round for all k views (modeled costs ∝ rows touched).

        Its spans, children of the caller's (`wal.commit` when served):
        `round.fetch` pulls the stored model to the host (and so waits for
        the device), `round.waters` is the Eq. 2 arithmetic, `round.update`
        puts the models and waters on the mesh and dispatches the band
        update, `round.sync` blocks on its band widths and each shard's
        streamed rows, and `round.reorganize` dispatches a reorganize that
        SKIING called. `round.update` counts `kernel_rounds` and
        `round.reorganize` counts `skiing.reorgs`."""
        m = self.metrics
        if self.skiing.should_reorganize():
            return self._do_reorg(state, W, b)
        with trace.span("round.fetch", metrics=m):
            W_s = np.asarray(state.W_stored)
            b_s = np.asarray(state.b_stored, np.float64)
        with trace.span("round.waters", metrics=m):
            self.lw, self.hw = waters_update(
                self.lw, self.hw, np.asarray(W, np.float32),
                np.asarray(b, np.float64), W_s, b_s, self.M, self.p)
        with trace.span("round.update", metrics=m):
            self.models = self._put_models(W, b)
            state = state._replace(lw=self._replicate(self.lw, np.float32),
                                   hw=self._replicate(self.hw, np.float32))
            labels, counts = self._update(state, *self.models)
            state = state._replace(labels=labels)
            self.kernel_rounds += 1
        with trace.span("round.sync", metrics=m):
            counts = np.asarray(counts)
        self._observe_window(counts[self.k:])
        self.skiing.record_incremental(
            float(np.sum(counts[:self.k])) / (self.n * self.k))
        return state

    def all_members(self, state) -> np.ndarray:
        return np.asarray(self._count(state))

    def real_rows(self, state: ShardedMultiViewState):
        """(gids, labels, eps) of the real rows on the host, in the shared
        clustering order: padding rows dropped."""
        gids = np.asarray(state.gids)
        real = gids != PAD_GID
        return (gids[real], np.asarray(state.labels)[:, real],
                np.asarray(state.eps)[:, real])

    def hybrid_labels_of(self, state: ShardedMultiViewState,
                         entity_id: int):
        """§3.5.2 batched single-entity read under the last round's models
        (`models`): the device-side waters probe resolves what it can from
        the eps-map; the views that miss are labelled from the entity's
        one feature row, in the same program. Returns ((k,) int8 labels,
        (k,) bool resolved-by-water mask).

        Span: `read.probe` (the program's dispatch and its one host
        copy)."""
        with trace.span("read.probe", metrics=self.metrics):
            st = state._replace(lw=self._replicate(self.lw, np.float32),
                                hw=self._replicate(self.hw, np.float32))
            out = np.asarray(self._probe(
                st, *self.models, self._replicate(entity_id, np.int32)))
        return out[0], out[1].astype(bool)
