"""Without a TPU, or without the system under test beside it, a run prints
no result and exits non-zero."""
import io
import shutil
import subprocess
import sys

import run


def test_refuses_without_a_tpu():
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(["--workload", "covtype-k7.train", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], out=out, err=err)
    assert rc == 2 and out.getvalue() == ""
    assert "tpu" in err.getvalue()


def test_refuses_an_unknown_cell():
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(["--workload", "nope.train", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], out=out, err=err)
    assert rc == 2 and out.getvalue() == ""


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "covtype-k7.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
