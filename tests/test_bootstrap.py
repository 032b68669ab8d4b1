"""Pre-jax bootstrap helpers of the launch CLIs (launch/_bootstrap.py)."""
import os

import pytest

from repro.launch._bootstrap import mesh_flag, use_compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_keeps_the_environment_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    assert use_compile_cache() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2"


def test_compile_cache_defaults_to_a_fixed_checkout_dir(monkeypatch):
    """Unset, the cache is ``<checkout>/.jax_cache``, the same path in
    every process, so a later process finds what an earlier one wrote."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    path = use_compile_cache()
    assert path == os.path.join(CHECKOUT, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert use_compile_cache() == path


@pytest.mark.parametrize("argv,want", [
    (["serve", "--mesh", "host"], "host"),
    (["serve", "--mesh=single", "--verify"], "single"),
    (["serve", "--verify"], None),
    (["serve", "--mesh"], None),
])
def test_mesh_flag(argv, want):
    assert mesh_flag(argv) == want
