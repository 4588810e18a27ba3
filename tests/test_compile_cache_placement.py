"""The one compile-cache placement rule (docs/aot_cache.md §compile cache
placement): ``$JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache`` — and nothing in code moves it."""

import os
import re
import tempfile

import jax
import pytest

from accelerate_tpu import (
    CompilationCacheKwargs,
    compilation_cache_dir,
    enable_compilation_cache,
)
from accelerate_tpu.native.aot_cache import AOTCompilationCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_jax_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_environment_wins_over_jax_cache_dir(tmp_path, monkeypatch, restore_jax_config):
    """A cache placed from outside stays put: ``CompilationCacheKwargs.
    jax_cache_dir`` arms the layer, it does not move it."""
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # as jax reads it at import
    AOTCompilationCache(CompilationCacheKwargs(
        cache_dir=str(tmp_path / "aot"), jax_cache_dir=str(tmp_path / "elsewhere"),
    ))
    assert jax.config.jax_compilation_cache_dir == placed
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == placed
    assert compilation_cache_dir(str(tmp_path / "elsewhere")) == placed


def test_unset_it_is_the_checkouts_jax_cache(monkeypatch, restore_jax_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    expected = os.path.join(REPO, ".jax_cache")
    assert compilation_cache_dir() == expected
    assert enable_compilation_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    # exported, so launch workers and smoke subprocesses share the cache
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == expected
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


def test_unset_jax_cache_dir_is_used_and_then_stays(tmp_path, monkeypatch, restore_jax_config):
    """No environment, a caller's directory: that one — and once placed it
    is the environment's, so a later caller cannot move it either."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = str(tmp_path / "first")
    assert enable_compilation_cache(first) == first
    assert enable_compilation_cache(str(tmp_path / "second")) == first


def test_the_default_is_never_a_temp_pid_or_timestamp_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(tempfile, "mkdtemp", lambda *a, **k: pytest.fail("mkdtemp"))
    one, two = compilation_cache_dir(), compilation_cache_dir()
    assert one == two  # fixed: the directory is part of what must be found again
    assert not one.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in one
    assert not re.search(r"\d{6,}", os.path.relpath(one, REPO))


def test_conftest_bench_and_smoke_all_go_through_the_helper():
    """No second placement anywhere: the three entry points call the helper,
    and the only other ``jax_compilation_cache_dir`` update in the tree is
    the profiler-armed disarm (``None``)."""
    for path in ("tests/conftest.py", "benchmark/harness.py", "chip_smoke.py"):
        with open(os.path.join(REPO, path), encoding="utf-8") as f:
            source = f.read()
        assert "enable_compilation_cache()" in source, path
        assert "jax_compilation_cache_dir" not in source, path
        assert "accelerate_tpu_jax_cache" not in source, path
    updates = []
    for root, _, files in os.walk(os.path.join(REPO, "accelerate_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name), encoding="utf-8") as f:
                    updates += re.findall(
                        r'config\.update\(\s*"jax_compilation_cache_dir",\s*([^)\s]+)',
                        f.read(),
                    )
    assert sorted(updates) == ["None", "path"]  # the disarm, and the helper
    # and the suite itself runs on the helper's placement
    assert jax.config.jax_compilation_cache_dir == os.environ["JAX_COMPILATION_CACHE_DIR"]
