"""What ``chip_smoke.py`` and the compile-cache helper promise off the chip:
no result without a TPU, no result without the package, and a fixed
compile-cache path."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _prints_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_chip_smoke_fails_without_a_tpu():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert not _prints_result(out.stdout)
    assert "not a TPU" in out.stderr


def test_chip_smoke_fails_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert not _prints_result(out.stdout)
    assert "repro package is missing" in out.stderr


@pytest.fixture()
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path,
                                            cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.CHECKOUT == ROOT
    path = compile_cache.use_compile_cache(tmp_path)
    assert path == str(tmp_path.resolve() / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_env(monkeypatch, tmp_path, cache_dir_restored):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert compile_cache.use_compile_cache(ROOT) == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == prev   # JAX reads the env
