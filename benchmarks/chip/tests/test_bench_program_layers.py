"""The readers of the program's own spans (`round_host_ms`,
`margin_share.read`): on a traced rehearsal they find the spans of the
profiled window; on known histograms they give the known reading; on a
program that records no profiled spans they give None."""
from types import SimpleNamespace

import pytest

import run
from rehearsal import rehearse


@pytest.mark.parametrize("cell,names", [
    ("covtype-k7.train", {"round_host_ms"}),
    ("citeseer-k16.read", {"margin_share.read"}),
])
def test_traced_rehearsal_reports_program_spans(cell, names):
    rc, result, err = rehearse(cell, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    assert names <= set(result["metrics"])
    assert all(result["metrics"][m]["value"] >= 0 for m in names)


def _read(metric, spans):
    """`metric`'s reading over a registry holding `spans`: name ->
    (profiled?, [seconds of each span])."""
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    for name, (profiled, seconds) in spans.items():
        key = f"{'profiled.' if profiled else ''}span.{name}.seconds"
        for s in seconds:
            reg.histogram(key).observe(s)
    reader = run.module_from(run.HERE / "layers" / f"{metric}.py").read
    return reader(SimpleNamespace(ex=SimpleNamespace(metrics=reg)))


@pytest.mark.parametrize("metric,spans,want", [
    # 4 profiled rounds, one of them reorganized: 2 + 1 + 0.5 + 0.5 ms of
    # host spans each; the waits on the device (sync, fetch) and the spans
    # outside the profile are left out
    ("round_host_ms", {"round.sgd": (True, [2e-3] * 4),
                       "round.waters": (True, [1e-3] * 4),
                       "round.update": (True, [0.5e-3] * 4),
                       "round.reorganize": (True, [2e-3]),
                       "round.sync": (True, [0.1] * 4),
                       "round.fetch": (True, [0.1] * 4),
                       "wal.commit": (True, [0.3] * 4)}, 4.0),
    ("round_host_ms", {"round.sgd": (False, [1.0] * 9)}, None),
    ("round_host_ms", {"wal.commit": (True, [0.3] * 4)}, None),
    ("margin_share.read", {"read.probe": (True, [1e-3] * 200),
                           "read.margin": (True, [8e-3] * 50),
                           "read.probe.x": (False, [1.0] * 300)}, 25.0),
    ("margin_share.read", {"read.probe": (True, [1e-3] * 200)}, 0.0),
    ("margin_share.read", {"read.probe": (False, [1e-3] * 200),
                           "read.margin": (False, [8e-3] * 50)}, None),
])
def test_program_span_readers(metric, spans, want):
    got = _read(metric, spans)
    assert got == (pytest.approx(want) if want is not None else None)
