"""_compile_cache.enable_compile_cache: the two placement rules.

``JAX_COMPILATION_CACHE_DIR`` set -> no code path sets another
directory (jax's own reading of the variable stands); unset -> the
cache is ``<checkout>/.jit_cache``, a fixed path."""

import os

import jax
import pytest

from apex_tpu import _compile_cache
from apex_tpu._compile_cache import enable_compile_cache


@pytest.fixture
def restore_cache_config():
    before_dir = jax.config.jax_compilation_cache_dir
    before_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before_min)
    from jax._src import compilation_cache as jax_cc

    jax_cc.reset_cache()


def test_env_set_means_no_directory_is_set_in_code(
        monkeypatch, restore_cache_config):
    """jax read the variable at import; a sentinel in the config must
    survive the call untouched — nothing in code overrides it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    jax.config.update("jax_compilation_cache_dir", "/from/the/env")
    assert enable_compile_cache(min_compile_secs=0.25) == "/from/the/env"
    assert jax.config.jax_compilation_cache_dir == "/from/the/env"
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == 0.25)


@pytest.mark.parametrize("value", [None, ""])
def test_env_unset_uses_the_checkout_jit_cache(
        monkeypatch, restore_cache_config, value):
    if value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    want = os.path.join(root, ".jit_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # stable by construction: no pid, temp name or clock in the path
    assert _compile_cache.default_cache_dir() == want
