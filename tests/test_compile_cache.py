"""launch/compile_cache.enable: the environment's cache directory when one is
set, otherwise one fixed directory inside the checkout."""

import os

import jax

from repro.launch import compile_cache

KEY = "jax_compilation_cache_dir"


def test_environment_directory_wins(monkeypatch, tmp_path):
    before = getattr(jax.config, KEY)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert getattr(jax.config, KEY) == before  # JAX reads the variable itself


def test_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = getattr(jax.config, KEY)
    try:
        path = compile_cache.enable()
        assert getattr(jax.config, KEY) == path
    finally:
        jax.config.update(KEY, before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
