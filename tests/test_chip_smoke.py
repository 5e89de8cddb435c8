"""``chip_smoke.py`` and the compile-cache location, on the CPU.

The script refuses to start without a TPU; its phases are run here in
process at a small scale, so that a change which breaks one of them fails
on the CPU before it costs a chip run. ``enable_compile_cache`` keeps
JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, and at a
fixed directory of the checkout when it is unset.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra, cwd):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    args = (code_or_args if isinstance(code_or_args, list)
            else [sys.executable, "-c", code_or_args])
    return subprocess.run(args, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    out = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")], {},
               tmp_path)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr and "cpu" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "SCALE", 8)
    return mod


def test_chip_smoke_phases_pass_at_small_scale(smoke, capsys):
    devices = jax.devices()
    with smoke.CompileClock() as clock:
        wg = smoke.build_graph(0)
        refs = smoke.References(wg)
        roots = smoke.phase_bfs(wg, refs, clock, 0, devices)
        smoke.phase_sssp(wg, refs, clock, roots[:smoke.SSSP_SOURCES],
                         devices)
        smoke.phase_served(wg, refs, clock, 0, devices)
    assert clock.programs > 0 and clock.seconds > 0
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[0] for ln in lines] == ["[graph]", "[bfs]", "[sssp]",
                                               "[served]"]
    assert "validated_keys=8" in lines[1]


def test_compile_cache_follows_the_environment(tmp_path):
    cache = tmp_path / "cache"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()")
    out = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(cache),
                      "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
               tmp_path)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)")
    out = _run(code, {}, tmp_path)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [REPO_CACHE_DIR, REPO_CACHE_DIR]
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
