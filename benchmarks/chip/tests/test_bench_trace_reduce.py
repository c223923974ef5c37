"""The trace reduction on hand-made events with known answers, one chip's
and two chips', and on a small trace recorded on one TPU v5e
(`data/v5e_trace.json.gz`: four maintenance rounds and four point reads of
the sharded engine)."""
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


MS = 1_000_000


def test_two_chips_in_lockstep_read_one_chip():
    # each chip runs a 1 ms op at 0 ms and at 5 ms of a 10 ms window: each
    # is busy 2 ms (a union over both planes would read 1 ms a chip)
    ev = {"device_planes": ["/device:TPU:0", "/device:TPU:1"],
          "device": [["fusion", t * MS, MS, p] for p in (0, 1) for t in (0, 5)],
          "host": []}
    r = trace_reduce.reduce(ev, "multiview_band_reclassify",
                            window_ns=(0, 10 * MS))
    assert r["busy_s"] == pytest.approx(0.002)
    assert r["device_ops"] == [["fusion", pytest.approx(0.002)]]
    gaps = dict(r["idle_gaps"])
    assert gaps["none (all gaps)"] == pytest.approx(0.008)
    assert gaps["none (longest gap)"] == pytest.approx(0.004)


def test_each_plane_is_reduced_on_its_own():
    k = "multiview_band_reclassify.1"
    ev = {"device_planes": ["/device:TPU:0", "/device:TPU:1"],
          # chip 0: [0, 1) kernel, [5, 6); chip 1: [0, 1) kernel, [2, 3)
          "device": [[k, 0, MS, 0], ["fusion", 5 * MS, MS, 0],
                     [k, 0, MS, 1], ["fusion", 2 * MS, MS, 1]],
          "host": [["commit", 0, 4 * MS], ["read", 4 * MS, 6 * MS]]}
    r = trace_reduce.reduce(ev, "multiview_band_reclassify",
                            window_ns=(0, 10 * MS))
    assert r["busy_s"] == pytest.approx(0.002)
    assert r["window_s"] == pytest.approx(0.010)
    # sums over the chips: kernel_s / kernel_launches is one launch
    assert r["kernel_launches"] == 2
    assert r["kernel_s"] == pytest.approx(0.002)
    assert dict(r["device_ops"]) == {k: pytest.approx(0.001),
                                     "fusion": pytest.approx(0.001)}
    # chip 0 idles [1, 5) under commit and [6, 10) under read; chip 1
    # [1, 2) under commit and [3, 10) under read
    assert dict(r["idle_gaps"]) == {
        "commit (all gaps)": pytest.approx((4 + 1) / 2 * 1e-3),
        "commit (longest gap)": pytest.approx(0.004),
        "read (all gaps)": pytest.approx((4 + 7) / 2 * 1e-3),
        "read (longest gap)": pytest.approx(0.007)}


def test_an_event_without_a_plane_is_on_plane_0():
    # the second chip ran nothing: it is idle for the whole window
    ev = {"device_planes": ["/device:TPU:0", "/device:TPU:1"],
          "device": [["fusion", 0, 4 * MS]], "host": []}
    r = trace_reduce.reduce(ev, "multiview_band_reclassify",
                            window_ns=(0, 10 * MS))
    assert r["busy_s"] == pytest.approx(0.002)
    assert dict(r["idle_gaps"]) == {"none (all gaps)": pytest.approx(0.008),
                                    "none (longest gap)": pytest.approx(0.010)}


def test_recorded_v5e_trace_reads_as_one_chip_did():
    # what the reduction gave on this trace when it took the union over all
    # planes: with one plane, the per-chip reduction is the same, digit for
    # digit
    ev = trace_reduce.load_events(str(RECORDED))
    assert trace_reduce.reduce(ev, "multiview_band_reclassify") == {
        "busy_s": 0.002716322, "window_s": 0.041954237,
        "kernel_s": 0.001422682, "kernel_launches": 4,
        "device_ops": [["multiview_band_reclassify.1", 0.001422682],
                       ["fusion.1", 0.000232781],
                       ["broadcast_select_fusion", 0.000207444],
                       ["copy-done", 0.000182578], ["sort.0", 0.000172313],
                       ["abs_reduce_fusion", 0.000151352],
                       ["fusion", 0.000149271], ["fusion.2", 5.0711e-05],
                       ["broadcast_select_fusion.1", 3.9381e-05],
                       ["rev.1", 2.5287e-05]],
        "idle_gaps": [["commit (all gaps)", 0.027507829],
                      ["read (all gaps)", 0.011730086],
                      ["read (longest gap)", 0.003339972],
                      ["commit (longest gap)", 0.002171858]]}
