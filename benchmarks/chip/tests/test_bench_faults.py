"""A run whose timed path is broken underneath comes out not correct: once
for each fault a one-chip cell can have. (The exchange between chips does
not exist in a one-chip cell.) The control, the program's margin products
in one bfloat16 pass, fails the stored-margin limit."""
import pytest

import run
from rehearsal import rehearse


@pytest.fixture
def broken(monkeypatch):
    """Install a fault when the window opens, after set-up, or with
    at="setup" before the program builds its steps."""
    def install(fault, at="window"):
        phase = getattr(run.Run, at)

        def faulty(self):
            fault(monkeypatch)
            return phase(self)

        monkeypatch.setattr(run.Run, at, faulty)
    return install


def _state_unchanged(mp):
    from repro.core.sharded import ShardedMultiViewHazy
    mp.setattr(ShardedMultiViewHazy, "apply_models",
               lambda self, state, W, b: state)


def _half_batch(mp):
    from repro.core.facade import ShardedFacade
    original = ShardedFacade.insert_examples

    def half(self, ids, labels):
        h = max(1, len(ids) // 2)
        return original(self, list(ids)[:h], list(labels)[:h])
    mp.setattr(ShardedFacade, "insert_examples", half)


def _label_altered(mp):
    from repro.core.sharded import ShardedMultiViewHazy
    original = ShardedMultiViewHazy.apply_models

    def flipped(self, state, W, b):
        state = original(self, state, W, b)
        return state._replace(labels=state.labels.at[0].multiply(-1))
    mp.setattr(ShardedMultiViewHazy, "apply_models", flipped)


def _answer_altered(mp):
    from repro.core.facade import ShardedFacade
    original = ShardedFacade.point_label

    def flipped(self, entity_id, view=0):
        lab, how = original(self, entity_id, view)
        return -lab, how
    mp.setattr(ShardedFacade, "point_label", flipped)


@pytest.mark.parametrize("cell,fault,check", [
    ("covtype-k7.train", _state_unchanged, "label_mismatch"),
    ("covtype-k7.train", _half_batch, "model_err"),
    ("covtype-k7.train", _label_altered, "label_mismatch"),
    ("citeseer-k16.read", _answer_altered, "answer_mismatch"),
    ("citeseer-k16.mixed", _half_batch, "model_err"),
])
def test_fault_makes_the_run_incorrect(broken, cell, fault, check):
    broken(fault)
    rc, result, err = rehearse(cell)
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    c = result["checks"][check]
    assert c["value"] > c["limit"]


class _Bf16Einsum:
    """jax.numpy whose einsum rounds float32 operands to bfloat16 first:
    one bf16 pass with f32 accumulation, what XLA's default precision
    gives on a TPU."""

    def __init__(self, jnp):
        self._jnp = jnp

    def __getattr__(self, name):
        return getattr(self._jnp, name)

    def einsum(self, spec, *operands, **kw):
        j = self._jnp
        operands = [o.astype(j.bfloat16).astype(j.float32)
                    if o.dtype == j.float32 else o for o in operands]
        return j.einsum(spec, *operands, **kw)


def _margins_in_bf16(mp):
    import repro.core.sharded as sharded
    mp.setattr(sharded, "jnp", _Bf16Einsum(sharded.jnp))


def test_control_fails_the_stored_margin_limit(broken):
    broken(_margins_in_bf16, at="setup")
    rc, result, err = rehearse("covtype-k7.train")
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    c = result["checks"]["eps_err"]
    assert c["value"] > c["limit"]
