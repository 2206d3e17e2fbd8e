"""JAX's persistent compilation cache, kept where later runs find it again.

A cache entry's key includes nothing of where the cache lives, but a cache
that moves between runs is never read back. So the directory is either the
one the environment names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself) or a fixed path inside the checkout. Entry points call
:func:`enable_compile_cache` once, before their first compile; importing
the package never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIRNAME = ".jax_cache"


def compile_cache_dir(root: str | os.PathLike) -> Path:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<root>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else Path(root).resolve() / DEFAULT_DIRNAME


def enable_compile_cache(root: str | os.PathLike) -> Path:
    """Turn the persistent cache on for every compile of this process and
    return its directory (see :func:`compile_cache_dir`).

    The directory is set in code only when the environment names none. All
    compiles are cached, however short: a kernel compiles in about a second,
    and a cold run compiles dozens of them.
    """
    path = compile_cache_dir(root)
    if ENV_VAR not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: str | os.PathLike) -> int:
    """Number of compiled programs stored in the cache directory."""
    p = Path(path)
    return sum(1 for _ in p.glob("*-cache")) if p.is_dir() else 0
