"""The persistent compilation cache is placed from outside, or at a fixed
path in the checkout."""
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]

# compiles one function in a fresh process, with the checkout's cache
# directory moved to argv[1] so the test does not write into the repo
CHILD = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE = pathlib.Path(sys.argv[1])
compile_cache.use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda a: a * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _compile_in_child(checkout_dir, env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    subprocess.run([sys.executable, "-c", CHILD, str(checkout_dir)],
                   env=env, check=True, timeout=120)


def test_env_dir_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_dir_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
        assert pathlib.Path(got) == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compiles_written_to_env_dir_only(tmp_path):
    env_dir, checkout_dir = tmp_path / "env", tmp_path / "checkout"
    _compile_in_child(checkout_dir, env_dir)
    assert any(p.name.endswith("-cache") for p in env_dir.iterdir())
    assert not checkout_dir.exists()


def test_compiles_written_to_checkout_dir_otherwise(tmp_path):
    checkout_dir = tmp_path / "checkout"
    _compile_in_child(checkout_dir)
    assert any(p.name.endswith("-cache") for p in checkout_dir.iterdir())
