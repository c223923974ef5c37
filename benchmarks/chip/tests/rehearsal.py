"""Shared helpers: one rehearsal run of a cell on the CPU, at the tiny size
its configuration's `rehearsal` object gives. A one-chip cell runs
in-process; a cell on more chips runs in a child process whose CPU backend
has that many devices."""
import io
import json
import os
import shutil
import subprocess
import sys
import textwrap

import run

SEED = 2**31 + 12345          # larger than 32 signed bits hold

# the child of a multi-chip rehearsal: the same run, the same last line
CHILD = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, sys.argv[1])
    import run
    sys.exit(run.run_cell(json.loads(sys.argv[2]),
                          rehearsal=json.loads(sys.argv[3]),
                          root=Path(sys.argv[4])))
""")


def manifest(root=run.ROOT):
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(cell, root=run.ROOT):
    return {w["name"]: w for w in manifest(root)["workloads"]}[cell]


def temp_root(path):
    """A checkout at `path` that holds what a run reads: BENCHMARK.json,
    this benchmark's files and the system under test (`src`, linked)."""
    shutil.copy(run.ROOT / "BENCHMARK.json", path)
    shutil.copytree(run.HERE, run.bench_dir(path),
                    ignore=shutil.ignore_patterns("__pycache__"))
    (path / "src").symlink_to(run.ROOT / "src", target_is_directory=True)
    return path


def rehearse(cell, *, trace=0, seconds=1.0, seed=SEED, root=run.ROOT):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    w = workload(cell, root)
    entry = {c["name"]: c for c in manifest(root)["configs"]}[w["config"]]
    size = json.loads((root / entry["file"]).read_text()).get("rehearsal")
    if size is None:
        return 2, None, (f"configuration {w['config']!r} ({entry['file']}) "
                         f"has no 'rehearsal' object: the CPU sizes its "
                         f"cells are rehearsed at")
    if int(w["chips"]) > 1:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=("--xla_force_host_platform_device_count="
                              f"{w['chips']}"))
        proc = subprocess.run(
            [sys.executable, "-c", CHILD, str(run.HERE), json.dumps(argv),
             json.dumps(size), str(root)],
            capture_output=True, text=True, timeout=600, env=env)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    else:
        o, e = io.StringIO(), io.StringIO()
        rc = run.run_cell(argv, rehearsal=size, root=root, out=o, err=e)
        out, err = o.getvalue(), e.getvalue()
    lines = out.splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, err
