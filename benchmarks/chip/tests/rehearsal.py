"""Shared helpers: one rehearsal run of a cell on the CPU, in-process."""
import io
import json

import run

# tiny sizes for the CPU; everything else is the cell as committed
SIZES = {
    "citeseer-k16": {"rows": 1024, "warm_scale": 0.25,
                     "table": {"hash_dim": 512, "topic_columns": 8}},
    "covtype-k7": {"rows": 4096, "warm_scale": 0.25},
}
SEED = 2**31 + 12345          # larger than 32 signed bits hold


def manifest():
    return json.loads((run.ROOT / "BENCHMARK.json").read_text())


def config_of(cell):
    return {w["name"]: w for w in manifest()["workloads"]}[cell]["config"]


def rehearse(cell, *, trace=0, seconds=1.0, seed=SEED, size=None):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      rehearsal=size or SIZES[config_of(cell)],
                      out=out, err=err)
    lines = out.getvalue().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, err.getvalue()
