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


# Backend compile requests this process has made, loads from the persistent
# cache included (a load deserializes, and JAX reports it as a compile). One
# cell, so that a reader keeps the reference and pays an index per read.
COMPILE_EVENTS = [0]
_watching = False


def watch_compiles() -> None:
    """Count this process's compile requests into ``COMPILE_EVENTS`` from
    JAX's own monitoring events (imports JAX: the device-backed scheduler
    calls it). A slow stage says from it whether a compile ran inside."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax.monitoring as mon

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE_EVENTS[0] += 1

    mon.register_event_duration_secs_listener(on_duration)
