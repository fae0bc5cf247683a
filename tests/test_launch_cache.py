"""The persistent compilation cache is placed from outside: the environment
variable wins, else one fixed directory inside the checkout."""

import os

import jax
import pytest

from repro.launch import cache as cache_lib


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_stays_in_charge(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(cache_lib.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache_lib.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache_lib.ENV, raising=False)
    path = cache_lib.use_compile_cache()
    assert path == cache_lib.DEFAULT_DIR == cache_lib.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.dirname(path) == cache_lib.REPO_ROOT
    assert os.path.isfile(os.path.join(cache_lib.REPO_ROOT, "chip_smoke.py"))
