"""The chip smoke script's CPU rehearsal, run in-process at its tiny size,
and the persistent compilation cache its entry points share."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")


@pytest.fixture
def chip_smoke(monkeypatch):
    """The script's module, with the persistent compile cache left off: a
    test must not switch it on for the rest of its worker process."""
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    return chip_smoke


def test_chip_smoke_cpu_rehearsal(chip_smoke, capsys):
    """The served path end to end at the rehearsal size: statements over
    the socket, kernel rounds and a reorganize, every answer checked
    against the numpy reference; the last line is the JSON verdict."""
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal runs on the CPU backend")
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    text = "\n".join(out)
    assert "n = 1,000 real rows, d = 1024, k = 16" in text
    assert "mismatches outside tol 0," in text
    assert "statement errors: 0" in text


def test_chip_smoke_refuses_without_a_chip(chip_smoke, capsys):
    """Without --cpu-rehearsal the script needs a TPU: on any other backend
    it exits non-zero before doing work and prints no verdict."""
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) != 0
    assert "ok" not in capsys.readouterr().out


def test_compile_cache_dir_env_or_fixed_repo_path():
    from repro.launch.compile_cache import ENV_VAR, compile_cache_dir
    assert compile_cache_dir({ENV_VAR: "/cache/here"}) == "/cache/here"
    fixed = compile_cache_dir({})
    assert fixed == os.path.join(ROOT, ".jax_cache")
    assert compile_cache_dir({ENV_VAR: ""}) == fixed == compile_cache_dir({})


def test_enable_compile_cache_leaves_a_set_env_var_alone(monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: with it set, the helper
    reports it and changes no setting."""
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    monkeypatch.setenv(ENV_VAR, "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_enable_compile_cache_defaults_to_the_repo_path(monkeypatch):
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_entry_points_do_not_enable_the_cache_on_import():
    """Importing an entry point or the helper sets nothing; only their
    main() turns the cache on."""
    import subprocess
    code = ("import jax, repro.launch.compile_cache, repro.launch.serve; "
            "import chip_smoke; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"
