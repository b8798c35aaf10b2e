"""The persistent compilation cache goes where the environment says, else
to one fixed, git-ignored directory inside the checkout."""
import pathlib

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_ignored_dir_in_the_checkout(monkeypatch,
                                                        restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable()
    root = pathlib.Path(__file__).resolve().parent.parent
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable() == got          # stable across calls
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
