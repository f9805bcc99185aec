"""CPU checks of ``chip_smoke.py``'s own rules: it refuses any backend but a
TPU, refuses to run outside a checkout, and places the compile cache where
``JAX_COMPILATION_CACHE_DIR`` says or at the checkout's fixed path."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def test_exits_nonzero_on_cpu_and_names_the_platform():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_exits_nonzero_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(lone, tmp_path)
    assert out.returncode != 0
    assert "not a checkout" in out.stderr
    assert out.stdout == ""


@pytest.fixture
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    was = jax.config.jax_compilation_cache_dir
    yield mod
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(chip_smoke, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = chip_smoke.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_the_environment(chip_smoke, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert chip_smoke.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the variable itself
