"""Every cell, rehearsed on the CPU at its configuration's rehearsal size,
prints a well-formed last line; a traced run adds the per-layer metrics,
busy and window time and the breakdown. A configuration or a four-chip
cell added as new files and entries is rehearsed the same way."""
import json

import pytest

import run
from rehearsal import manifest, rehearse, temp_root, workload

CELLS = [w["name"] for w in manifest()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def reported(kind, cell, root=run.ROOT):
    return {m["name"]: m["unit"] for m in manifest(root)[kind]
            if cell in m.get("workloads", [cell])}


def check_well_formed(cell, root=run.ROOT):
    rc, result, err = rehearse(cell, root=root)
    assert rc == 0, err[-2000:]
    assert KEYS <= set(result) and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    want = reported("end_to_end", cell, root)
    assert {m: v["unit"] for m, v in result["metrics"].items()} == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    dev = result["device"]
    assert dev["platform"] == "cpu"
    assert dev["count"] == workload(cell, root)["chips"]
    assert "memory_peak_bytes" in dev
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and line.endswith(" ok")
               for line in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_last_line(cell):
    check_well_formed(cell)


def _add_cell(root, like, new):
    """`new`: a copy of the cell `like` (with `new`'s fields) in the root's
    BENCHMARK.json, reporting every metric that `like` reports."""
    m = manifest(root)
    m["workloads"].append({**workload(like, root), **new})
    for metric in m["end_to_end"] + m["per_layer"]:
        if like in metric.get("workloads", []):
            metric["workloads"].append(new["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=1))


def test_a_four_chip_cell_is_rehearsed_on_four_devices(tmp_path):
    """A `chips: 4` cell runs in a child process whose CPU backend has four
    devices. Until the program can place a view on a mesh (the catalog
    builds a (1, 1) mesh), the run serves the view from one of the four."""
    root = temp_root(tmp_path)
    _add_cell(root, "covtype-k7.train",
              {"name": "covtype-k7.train4", "chips": 4})
    check_well_formed("covtype-k7.train4", root)


def test_a_new_configuration_needs_no_test_edit(tmp_path):
    """A configuration added as its own files (config with its `rehearsal`
    object, limits) and entries is rehearsed by the same check as every
    committed cell."""
    root = temp_root(tmp_path)
    bench = run.bench_dir(root)
    cfg = json.loads((bench / "configs" / "covtype-k7.json").read_text())
    cfg.update(name="covtype-k3", k=3,
               rehearsal={"rows": 2048, "warm_scale": 0.25})
    cfg["table"]["class_counts"] = [60000, 30000, 10000]
    (bench / "configs" / "covtype-k3.json").write_text(json.dumps(cfg))
    (bench / "limits" / "covtype-k3.json").write_text(
        (bench / "limits" / "covtype-k7.json").read_text())
    m = manifest(root)
    like = {c["name"]: c for c in m["configs"]}["covtype-k7"]
    m["configs"].append({**like, "name": "covtype-k3",
                         "file": "benchmarks/chip/configs/covtype-k3.json"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    _add_cell(root, "covtype-k7.train",
              {"name": "covtype-k3.train", "config": "covtype-k3"})
    check_well_formed("covtype-k3.train", root)


def test_a_configuration_without_rehearsal_sizes_is_named(tmp_path):
    root = temp_root(tmp_path)
    path = run.bench_dir(root) / "configs" / "covtype-k7.json"
    cfg = json.loads(path.read_text())
    del cfg["rehearsal"]
    path.write_text(json.dumps(cfg))
    rc, result, err = rehearse("covtype-k7.train", root=root)
    assert rc != 0 and result is None
    assert "'covtype-k7'" in err and "'rehearsal'" in err


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
