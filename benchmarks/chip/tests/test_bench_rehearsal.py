"""Every cell, rehearsed on the CPU at a tiny size, prints a well-formed
last line; a traced run adds the per-layer metrics, busy and window time
and the breakdown."""
import pytest

from rehearsal import manifest, rehearse

CELLS = [w["name"] for w in manifest()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def reported(kind, cell):
    return {m["name"]: m["unit"] for m in manifest()[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_last_line(cell):
    rc, result, err = rehearse(cell)
    assert rc == 0, err[-2000:]
    assert KEYS <= set(result) and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    want = reported("end_to_end", cell)
    assert {m: v["unit"] for m, v in result["metrics"].items()} == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert "memory_peak_bytes" in dev
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and line.endswith(" ok")
               for line in tail)


@pytest.mark.parametrize("cell", ["citeseer-k16.train", "citeseer-k16.mixed"])
def test_traced_rehearsal_reports_layers(cell):
    rc, result, err = rehearse(cell, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    want = reported("per_layer", cell)
    assert set(result["metrics"]) <= set(want)
    # the host-side readers always find something; the band kernel's
    # readers need the compiled kernel in a TPU trace
    idle = {m for m in want if m.startswith("device_idle.")}
    assert idle and idle <= set(result["metrics"])
    dev = result["device"]
    assert dev["busy_s"] > 0 and dev["window_s"] > 0
    assert result["breakdown"]["device_ops"]
    assert len(result["breakdown"]["device_ops"]) <= 10
