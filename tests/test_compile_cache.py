"""Entry points' persistent compilation cache: where it lands."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_cache_dir_follows_env_else_checkout(monkeypatch, restore_cache_dir,
                                             env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env is None:
        assert got == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:  # JAX reads the variable itself; nothing else is set
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before


def test_importing_the_library_sets_no_cache():
    code = ("import jax, repro.launch.server, repro.launch.serve, "
            "repro.launch.train; "
            "assert jax.config.jax_compilation_cache_dir is None")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
