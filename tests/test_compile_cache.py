"""launch.compile_cache: the env var wins; otherwise one fixed directory."""

import os
import subprocess
import sys

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_is_fixed_checkout_dir(monkeypatch, tmp_path):
    assert compile_cache.CHECKOUT == ROOT
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    # point the checkout elsewhere so this process never writes into it
    monkeypatch.setattr(compile_cache, "CHECKOUT", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(str(tmp_path), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compiled_programs_land_in_env_dir(tmp_path):
    script = ("import jax, jax.numpy as jnp\n"
              "from repro.launch.compile_cache import enable_compile_cache\n"
              "enable_compile_cache()\n"
              "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8)))"
              ".block_until_ready()\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.listdir(tmp_path)
