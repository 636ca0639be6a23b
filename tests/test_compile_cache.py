"""Where the entry points put JAX's persistent compilation cache."""
import os

import jax

from repro.common.compile_cache import (CHECKOUT_CACHE_DIR,
                                        configure_compile_cache)

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_one_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        assert configure_compile_cache() == got      # fixed, not per call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.samefile(os.path.dirname(got), ROOT)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert f"{CHECKOUT_CACHE_DIR.name}/" in f.read().split()
