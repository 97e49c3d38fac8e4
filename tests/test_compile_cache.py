"""Where the entry points keep JAX's persistent compilation cache."""
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

CHILD = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)))
"""


def test_cache_dir_follows_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.cache_dir() == str(REPO / ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.cache_dir()


def test_enabled_cache_is_written_where_environment_says(tmp_path):
    cache = tmp_path / "cache"
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=300, cwd=str(tmp_path),
                       env={"PYTHONPATH": str(REPO / "src"),
                            "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu",
                            "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir()), "no compiled program was cached"
