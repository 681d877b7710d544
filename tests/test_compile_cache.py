"""The compile-cache helper (kubernetes_tpu/utils/compile_cache.py):
JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code; without
it the cache lives at one fixed path inside the checkout."""

import os
import tempfile
import time

import jax
import pytest

from kubernetes_tpu.utils import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    session's own compile configuration stays untouched."""
    calls = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.__setitem__(name, value)
    )
    return calls


def test_env_var_wins_and_no_directory_is_set_in_code(
    monkeypatch, config_updates
):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.configure_compile_cache() == "/some/dir"
    # JAX reads the variable itself: code names no directory at all
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_the_fixed_in_repo_path(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.configure_compile_cache()
    assert first == os.path.join(REPO_ROOT, ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == first
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    # nothing from a pid, a clock or a tempdir: another process at
    # another time computes the same path
    monkeypatch.setattr(os, "getpid", lambda: 424242)
    monkeypatch.setattr(time, "time", lambda: 1e9)
    monkeypatch.setattr(tempfile, "gettempdir", lambda: "/elsewhere")
    assert compile_cache.configure_compile_cache() == first
    assert not first.startswith(tempfile.gettempdir() + os.sep)
    assert "424242" not in first


def test_cache_dir_is_git_ignored():
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_stats_count_requests_and_hits():
    before = compile_cache.compile_cache_stats()
    compile_cache._counter._on_event(compile_cache._REQUEST_EVENT)
    compile_cache._counter._on_event(compile_cache._REQUEST_EVENT)
    compile_cache._counter._on_event(compile_cache._HIT_EVENT)
    after = compile_cache.compile_cache_stats()
    assert after["requests"] - before["requests"] == 2
    assert after["hits"] - before["hits"] == 1
    assert after["misses"] - before["misses"] == 1
