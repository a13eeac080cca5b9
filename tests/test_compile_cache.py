"""Where the persistent compilation cache lands: in
``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``<repo>/.jax_cache``."""

import os
import pathlib
import subprocess
import sys
import uuid

import jax

from repro.launch import cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_the_repo(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _entries(path: pathlib.Path) -> set[str]:
    return set(os.listdir(path)) if path.exists() else set()


def _compile_once(env_dir: str | None) -> None:
    """Compile one new program (a fresh constant, so a fresh cache key) in
    a process that placed its cache with ``enable_compile_cache``."""
    code = f"""
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
enable_compile_cache()
jax.jit(lambda x: x * {uuid.uuid4().int % 10**6} + 1)(jnp.ones(8)).block_until_ready()
"""
    env = {k: v for k, v in os.environ.items() if k != cache.ENV_VAR}
    if env_dir is not None:
        env[cache.ENV_VAR] = env_dir
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           env.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_entries_land_where_the_cache_was_placed(tmp_path):
    placed = tmp_path / "cache"
    default_before = _entries(cache.DEFAULT_DIR)
    _compile_once(str(placed))
    assert _entries(placed)
    assert _entries(cache.DEFAULT_DIR) == default_before

    _compile_once(None)
    assert _entries(cache.DEFAULT_DIR) - default_before
