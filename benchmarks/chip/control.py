#!/usr/bin/env python3
"""The control of a cell's correctness check, on several seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 3

The program runs its margin products at XLA's default precision (on a TPU,
one bf16 pass) in place of `HIGHEST`: its own lower-precision path, switched
on by setting `repro.core.sharded.HIGHEST` before any step is built. Each
seed is then one run of the cell (`run.py`) at the cell's own size and load,
with a short window; its checks should fail.

The benchmark's own runs never run this. The last line is one JSON object
with every seed's checks.
"""
from __future__ import annotations

import argparse
import io
import json
import sys

import run


def lower_program_precision() -> None:
    """Switch the program's margin products to XLA's default precision."""
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    import repro.core.sharded as sharded
    if not hasattr(sharded, "HIGHEST"):
        raise SystemExit("control.py: repro.core.sharded has no HIGHEST; the "
                         "control cannot lower the program's precision")
    sharded.HIGHEST = jax.lax.Precision.DEFAULT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    lower_program_precision()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = io.StringIO()
        rc = run.run_cell(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds), "--trace", "0"],
                          out=out, err=sys.stderr)
        text = out.getvalue()
        print(text, end="", flush=True)
        last = json.loads(text.splitlines()[-1]) if rc == 0 else {}
        rows.append({"seed": seed, "rc": rc,
                     "correct": last.get("correct"),
                     "checks": {k: v["value"] for k, v in
                                last.get("checks", {}).items()}})
        print(f"control seed {seed}: {json.dumps(rows[-1])}", flush=True)
    print(json.dumps({"workload": args.workload, "program_precision": "default",
                      "runs": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
