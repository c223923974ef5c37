"""Single-source + host/device parity tests for the functional core.

PR 3's load-bearing claims:

  * every algorithm rule (Lemma 3.1 partition, Eq. 2 waters update, SKIING
    charge rule) exists exactly ONCE, in `core/engine.py`, and the three
    stateful shells (hazy / multiview / sharded) import it rather than
    re-deriving it — asserted structurally below;
  * the pure `EngineState` steps are the executable specification of the
    shells: the same random insert stream driven through the NumPy
    `MultiViewEngine` shell, the numpy functional core and the *jitted*
    functional core produces identical labels, counts, waters, pending
    masks and reorg schedules under every policy (eager, lazy, hybrid) —
    the hypothesis trajectory test below.
"""
import functools
import inspect

import numpy as np
import pytest

import repro.core.engine as E
import repro.core.hazy as hazy_mod
import repro.core.multiview as mv_mod
import repro.core.sharded as sh_mod
import repro.core.skiing as sk_mod
import repro.core.waters as w_mod
from repro.core import MultiViewEngine

try:                    # property version runs when hypothesis is available;
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:     # the fixed-case sweep below always runs
    HAVE_HYPOTHESIS = False

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N, D, K = 256, 16, 3


# ---------------------------------------------------------------------------
# Single-source regressions: the shells must IMPORT the rules, not re-derive
# ---------------------------------------------------------------------------

def test_single_source_band_partition():
    """One Lemma 3.1 partition: hazy/multiview/sharded all bind the very
    function objects from engine.py (the sorted-row form on the host, the
    shared-order covering windows on the device, the point-probe form in
    all three), and none of them re-derives the partition with a raw
    searchsorted against the waters."""
    assert hazy_mod.band_partition is E.band_partition
    assert mv_mod.band_partition is E.band_partition
    assert hazy_mod.probe_partition is E.probe_partition
    assert mv_mod.probe_partition is E.probe_partition
    assert sh_mod.probe_partition is E.probe_partition
    assert sh_mod.covering_windows is E.covering_windows
    for mod in (hazy_mod, mv_mod, sh_mod):
        src = inspect.getsource(mod).replace(" ", "")
        assert "fromrepro.core.engineimport" in src
        assert "searchsorted(eps" not in src          # no re-derived partition
        assert "searchsorted(self.eps" not in src


def test_single_source_waters_and_skiing():
    """One Eq. 2 waters update and one SKIING charge rule: the shells and
    the scalar Waters/Skiing wrappers all delegate to engine.py."""
    assert mv_mod.waters_update is E.waters_update
    assert sh_mod.waters_update is E.waters_update
    assert w_mod.waters_update is E.waters_update
    assert mv_mod.skiing_charge is E.skiing_charge
    assert mv_mod.skiing_due is E.skiing_due
    assert sk_mod.skiing_charge is E.skiing_charge
    assert sk_mod.skiing_due is E.skiing_due
    assert "waters_update" in inspect.getsource(w_mod.Waters.update)
    assert "skiing_due" in inspect.getsource(sk_mod.Skiing.should_reorganize)
    assert "skiing_charge" in inspect.getsource(sk_mod.Skiing.record_incremental)


def test_covering_windows_cover_band():
    """The shared-order covering window is the tightest contiguous superset
    of the Lemma 3.1 band (the sharded kernel's window form)."""
    r = np.random.default_rng(0)
    eps = r.normal(size=(4, 64)).astype(np.float32)
    lw = -np.abs(r.normal(size=4))
    hw = np.abs(r.normal(size=4))
    hw[3] = lw[3]                                   # force one empty band
    start, end, width = E.covering_windows(eps, lw, hw)
    for v in range(4):
        members = np.flatnonzero(E.band_mask(eps[v], lw[v], hw[v]))
        assert width[v] == members.size
        if members.size:
            assert start[v] == members.min() and end[v] == members.max() + 1
        else:
            assert start[v] == 0 and end[v] == 0


# ---------------------------------------------------------------------------
# Host/device parity: shell == numpy core == jitted core, per policy
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted(policy: str, buffer_cap: int):
    """Jitted Layer 2 steps; M/alpha are traced so examples share compiles."""
    def mk(M, alpha):
        return E.EngineParams(M=M, p=2.0, alpha=alpha, buffer_cap=buffer_cap)

    @jax.jit
    def apply(state, W, b, M, alpha):
        return E.apply_model(state, W, b, mk(M, alpha), policy=policy, xp=jnp)

    @jax.jit
    def cu(state, touch, M, alpha):
        return E.catch_up(state, touch, mk(M, alpha), xp=jnp)

    @jax.jit
    def probe(state, eid, M, alpha):
        return E.hybrid_probe(state, eid, mk(M, alpha), xp=jnp)

    return apply, cu, probe


def _entity_order(labels, perm):
    labels, perm = np.asarray(labels), np.asarray(perm)
    out = np.empty_like(labels)
    for v in range(labels.shape[0]):
        out[v, perm[v]] = labels[v]
    return out


def _parity_trajectory(seed, policy, rounds):
    r = np.random.default_rng(seed)
    F = r.normal(size=(N, D)).astype(np.float32)
    F /= np.maximum(np.linalg.norm(F, axis=1, keepdims=True), 1e-9)
    bf = 0.06 if policy == "hybrid" else 0.0
    shell = MultiViewEngine(F, K, p=2.0, q=2.0, alpha=1.0, policy=policy,
                            cost_mode="modeled", buffer_frac=bf)
    params = E.make_params(F, p=2.0, q=2.0, alpha=1.0, buffer_frac=bf)
    st_np = E.init_state(F, K, params)
    st_j = jax.tree_util.tree_map(jnp.asarray, st_np)
    j_apply, j_cu, j_probe = _jitted(policy, params.buffer_cap)
    M, alpha = params.M, params.alpha
    ones = np.ones(K, bool)
    W = np.zeros((K, D), np.float32)
    b = np.zeros(K, np.float64)
    reorg_np = np.zeros(K, np.int64)
    reorg_j = np.zeros(K, np.int64)

    for t in range(rounds):
        W = (W + r.normal(size=(K, D)) * 0.05).astype(np.float32)
        b = b + r.normal(size=K) * 0.02
        shell.apply_models(W, b)
        st_np, inf_n = E.apply_model(st_np, W, b, params, policy=policy)
        st_j, inf_j = j_apply(st_j, jnp.asarray(W), jnp.asarray(b), M, alpha)
        reorg_np += np.asarray(inf_n["reorged"])
        reorg_j += np.asarray(inf_j["reorged"])
        if t % 7 == 3:                       # All-Members read on all sides
            counts = shell.all_members()
            st_np, cn = E.catch_up(st_np, ones, params)
            st_j, cj = j_cu(st_j, jnp.asarray(ones), M, alpha)
            reorg_np += np.asarray(cn["reorged"])
            reorg_j += np.asarray(cj["reorged"])
            assert np.array_equal(counts, st_np.pos_count)
            assert np.array_equal(counts, np.asarray(st_j.pos_count))
        if policy == "hybrid" and t % 5 == 2:
            for e in r.integers(0, N, 3):    # Fig. 8 probes on all sides
                labs, hows = shell.hybrid_labels_of(int(e))
                st_np, ln, tn = E.hybrid_probe(st_np, int(e), params)
                st_j, lj, tj = j_probe(st_j, jnp.int32(int(e)), M, alpha)
                assert np.array_equal(labs, ln) and np.array_equal(hows, tn)
                assert np.array_equal(labs, np.asarray(lj))
                assert np.array_equal(hows, np.asarray(tj))

    counts = shell.all_members()             # final catch-up everywhere
    st_np, cn = E.catch_up(st_np, ones, params)
    st_j, cj = j_cu(st_j, jnp.asarray(ones), M, alpha)
    reorg_np += np.asarray(cn["reorged"])
    reorg_j += np.asarray(cj["reorged"])

    ent_shell = _entity_order(shell.labels_sorted, shell.perm)
    assert np.array_equal(ent_shell, _entity_order(st_np.labels, st_np.perm))
    assert np.array_equal(ent_shell, _entity_order(st_j.labels, st_j.perm))
    assert np.array_equal(counts, st_np.pos_count)
    assert np.array_equal(counts, np.asarray(st_j.pos_count))
    assert np.array_equal(shell.pending, st_np.pending)
    assert np.array_equal(shell.pending, np.asarray(st_j.pending))
    # waters: bitwise vs the numpy core, tight allclose vs the f32 jit core
    np.testing.assert_array_equal(shell.lw, st_np.lw)
    np.testing.assert_array_equal(shell.hw, st_np.hw)
    np.testing.assert_allclose(np.asarray(st_j.lw), shell.lw,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_j.hw), shell.hw,
                               rtol=1e-5, atol=1e-6)
    # identical reorg schedules on all three execution paths
    assert np.array_equal(shell.reorg_counts, reorg_np)
    assert np.array_equal(shell.reorg_counts, reorg_j)
    assert shell.check_consistent()
    return shell


@pytest.mark.parametrize("seed,policy,rounds", [
    (11, "eager", 24), (12, "eager", 16),
    (21, "lazy", 24), (22, "lazy", 17),
    (31, "hybrid", 24), (32, "hybrid", 18),
])
def test_shell_core_jit_parity(seed, policy, rounds):
    """Fixed-case sweep (always runs): same stream through the NumPy shell,
    the numpy functional core and the jitted functional core."""
    shell = _parity_trajectory(seed, policy, rounds)
    assert shell.stats.rounds == rounds


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000),
           policy=st.sampled_from(["eager", "lazy", "hybrid"]),
           rounds=st.integers(12, 28))
    def test_shell_core_jit_parity_property(seed, policy, rounds):
        _parity_trajectory(seed, policy, rounds)
