"""Where the persistent compilation cache lives (`repro.runtime.
compile_cache`): the environment's ``JAX_COMPILATION_CACHE_DIR`` when set,
otherwise one fixed directory inside the checkout — never a name that
changes between runs, which would make every run compile from cold."""
import os

import jax
import pytest

from repro.runtime.compile_cache import (
    ENV_VAR,
    cache_entries,
    compile_cache_dir,
    enable_compile_cache,
)


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_var_wins(tmp_path, monkeypatch):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "shared"))
    assert compile_cache_dir(tmp_path / "repo") == tmp_path / "shared"


def test_fixed_path_inside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first = compile_cache_dir(tmp_path)
    monkeypatch.chdir(tmp_path.parent)
    assert compile_cache_dir(str(tmp_path)) == first == tmp_path / ".jax_cache"


def test_enable_sets_the_dir_only_when_the_env_names_none(
        tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    jax.config.update("jax_compilation_cache_dir", "/elsewhere")
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "shared"))
    assert enable_compile_cache(tmp_path) == tmp_path / "shared"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere"


def test_cache_entries_counts_compiled_programs(tmp_path):
    assert cache_entries(tmp_path / "missing") == 0
    for name in ("a-cache", "a-atime", "b-cache"):
        (tmp_path / name).write_bytes(b"")
    os.makedirs(tmp_path / "sub")
    assert cache_entries(tmp_path) == 2
