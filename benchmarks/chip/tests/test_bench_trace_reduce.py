"""The trace reduction on hand-made events with known answers, and on a
small trace recorded on one TPU v5e (`data/v5e_trace.json.gz`: four
maintenance rounds and four point reads of the sharded engine)."""
from pathlib import Path

import pytest

import trace_reduce

RECORDED = Path(__file__).parent / "data" / "v5e_trace.json.gz"


def test_busy_kernel_and_gaps_on_known_events():
    ev = {"device_planes": ["/device:TPU:0"],
          "device": [["fusion.1", 0, 10], ["multiview_band_reclassify.1", 20, 10],
                     ["fusion.2", 25, 10], ["copy.3", 50, 5]],
          "host": [["commit", 8, 15], ["read", 34, 30]]}
    r = trace_reduce.reduce(ev, "multiview_band_reclassify", window_ns=(0, 60))
    # busy: [0,10) + [20,35) + [50,55) = 30 ns of a 60 ns window
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(60e-9)
    assert r["kernel_launches"] == 1
    assert r["kernel_s"] == pytest.approx(10e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(10e-9)] or \
        r["device_ops"][0][1] == pytest.approx(10e-9)
    gaps = dict((n, v) for n, v in r["idle_gaps"])
    # idle: [10,20) under "commit", [35,50) and [55,60) under "read"
    assert gaps["commit (all gaps)"] == pytest.approx(10e-9)
    assert gaps["read (all gaps)"] == pytest.approx(20e-9)
    assert gaps["read (longest gap)"] == pytest.approx(15e-9)


def test_overlapping_ops_count_once():
    ev = {"device_planes": ["/device:TPU:0"],
          "device": [["a", 0, 100], ["b", 50, 100], ["c", 120, 30]],
          "host": []}
    r = trace_reduce.reduce(ev, "multiview_band_reclassify")
    assert r["busy_s"] == pytest.approx(150e-9)
    assert r["window_s"] == pytest.approx(150e-9)
    assert r["kernel_launches"] == 0 and r["idle_gaps"] == []


def test_recorded_v5e_trace():
    ev = trace_reduce.load_events(str(RECORDED))
    assert ev["device_planes"] == ["/device:TPU:0"]
    r = trace_reduce.reduce(ev, "multiview_band_reclassify")
    assert r["kernel_launches"] == 4
    assert 0 < r["kernel_s"] < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == 10
    assert all(" = " not in n for n, _ in r["device_ops"])
    assert {n.split(" (")[0] for n, _ in r["idle_gaps"]} <= {"commit", "read",
                                                           "none"}
