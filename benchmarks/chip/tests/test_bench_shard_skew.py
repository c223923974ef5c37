"""`band_shard_skew` on hand-made registries: the widest shard's rows over
the even split, from the launches of the profiled window alone; None where
the program keeps no such histograms or no launch fell in the window."""
from types import SimpleNamespace

import pytest

import run


def _read(launches, shards=4, profiled=True, view=True):
    """The reading over a registry that saw `launches`, each a list of the
    rows every shard streamed."""
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    prefix = "profiled." if profiled else ""
    for rows in launches:
        reg.histogram(f"{prefix}band.window_rows").observe(sum(rows))
        reg.histogram(f"{prefix}band.shard_window_rows_max").observe(
            max(rows))
    if view:
        reg.register_collector("view.topics", lambda: {"shards": shards})
    reader = run.module_from(run.HERE / "layers" / "band_shard_skew.py").read
    return reader(SimpleNamespace(ex=SimpleNamespace(metrics=reg),
                                  vname="topics"))


@pytest.mark.parametrize("launches,shards,want", [
    ([[256, 256, 256, 256]] * 3, 4, 1.0),                  # even
    ([[512, 128, 128, 256]], 4, 512 * 4 / 1024),           # one launch: 2.0
    # two launches weigh with their rows: 4 x (512 + 128) / (1024 + 512)
    ([[512, 128, 128, 256], [128] * 4], 4, 4 * 640 / 1536),
    ([[896]], 1, 1.0),                                     # one chip
])
def test_skew_of_known_launches(launches, shards, want):
    assert _read(launches, shards) == pytest.approx(want)


@pytest.mark.parametrize("kw", [
    {"launches": []},                                       # none in window
    {"launches": [[256] * 4], "profiled": False},           # outside it
    {"launches": [[256] * 4], "view": False},               # no view shards
])
def test_skew_is_none_without_launches_in_the_window(kw):
    assert _read(**kw) is None
