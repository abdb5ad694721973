"""The persistent compile cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set and ``<checkout>/.jax_cache``
otherwise — never a home directory, a temporary name, a pid or a time
(the machines that hold the chip keep no home, and a directory that
moves never hits)."""

import inspect
import os

import jax
import pytest

from openr_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_set_means_that_directory_and_no_other(
    tmp_path, monkeypatch, restore_cache_dir
):
    target = tmp_path / "outside" / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    # a home that must never be consulted
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    assert compile_cache.enable() == str(target)
    assert jax.config.jax_compilation_cache_dir == str(target)
    assert target.is_dir()
    assert not (tmp_path / "home").exists()
    assert not (tmp_path / "xdg").exists()


def test_env_unset_means_the_checkout(
    tmp_path, monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same answer every time: no pid, time or temp name in it
    assert compile_cache.enable() == want
    assert not (tmp_path / "home").exists()
    assert not (tmp_path / "xdg").exists()


def test_no_code_path_names_another_directory():
    """``enable`` takes no directory argument and the module reads no
    other variable: the only way to move the cache is the one jax
    itself documents."""
    assert not inspect.signature(compile_cache.enable).parameters
    src = inspect.getsource(compile_cache)
    code = "\n".join(
        line for line in src.split('"""')[2].splitlines()
        if not line.lstrip().startswith("#")
    )
    for forbidden in ("expanduser", "XDG_CACHE_HOME", "tempfile",
                      "getpid", "time.", "OPENR_"):
        assert forbidden not in code, forbidden
