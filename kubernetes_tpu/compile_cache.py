"""Where the persistent XLA compilation cache lives — one rule, JAX-free so
the harness side (which only builds child environments) can use it too.

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache. JAX reads
  the variable itself at import and this package configures no other.
- unset: ``<checkout>/.jax_cache`` — fixed, derived from the package path
  (the path is part of the cache key, so a directory that moves never hits)
  and listed in ``.gitignore``.

Child processes inherit the choice through the environment
(:func:`export`), so every process of a plane shares one cache.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory this process (and its children) cache compiles in."""
    return os.environ.get(ENV_VAR) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def export(env: dict) -> dict:
    """Pin the cache directory into a child's environment (in place)."""
    env.setdefault(ENV_VAR, cache_dir())
    return env


def entry_count(path: str = "") -> int:
    """Number of cached executables in the directory (0 when absent)."""
    try:
        return sum(1 for n in os.listdir(path or cache_dir())
                   if not n.endswith("-atime"))
    except OSError:
        return 0
