"""Where the persistent compilation cache lives.

JAX_COMPILATION_CACHE_DIR, when set, is JAX's own: the program sets no
directory. Unset, the cache goes to a fixed directory in the checkout,
so a later run finds what an earlier one compiled.
"""

import pathlib

import jax
import pytest

from galah_tpu.utils import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                                cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    sentinel = str(tmp_path / "as-jax-read-it")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == sentinel


def test_unset_env_uses_the_checkout_directory(monkeypatch,
                                               cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure_compile_cache()
    checkout = pathlib.Path(__file__).resolve().parent.parent
    assert got == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same directory every time: no pid, clock or temp name in it
    assert compile_cache.configure_compile_cache() == got
