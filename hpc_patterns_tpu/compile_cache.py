"""Where the persistent XLA compile cache lives — one rule for every
entry point (the apps' ``run_instrumented``, ``chipbench/run.py``,
``benchmarks/*.py``, ``__graft_entry__``, ``tests/conftest.py``).

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, and this
  module sets no directory in code — whoever placed the cache from
  outside (a machine that keeps one between runs) owns the location.
- unset: a fixed path inside the checkout, ``.cache/jax`` (git-ignored).
  The path is part of every cache key, so it is never a temp name, a
  pid or a time: a directory that moves never hits.

A cold chip run compiles for minutes; every later process of the same
command — and every later command on a machine that keeps the directory
— starts warm. Every program is cached, however quickly it compiled
(jax's default skips those under a second): a run is many processes
that each re-trace the same hundred small programs, and those add up.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_MIN_COMPILE_SECS = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".cache" / "jax"

_enabled = False


def cache_dir(subdir: str | None = None) -> Path:
    """The directory the compile cache uses: the environment's when
    ``JAX_COMPILATION_CACHE_DIR`` is set (``subdir`` ignored — the
    outside owner's layout), else :data:`DEFAULT_CACHE_DIR`, optionally
    a fixed ``subdir`` of it (the CPU test suite keys one by host CPU
    features)."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return Path(env)
    return DEFAULT_CACHE_DIR / subdir if subdir else DEFAULT_CACHE_DIR


def enable(subdir: str | None = None) -> Path:
    """Point jax's persistent compile cache at :func:`cache_dir`. Once
    per process: a later call returns the directory without touching
    the config again, so a caller that has since switched the cache
    off on purpose (a test forcing cold compiles) stays off."""
    global _enabled
    d = cache_dir(subdir)
    if not _enabled:
        _enabled = True
        import jax

        if not os.environ.get(ENV_CACHE_DIR):
            jax.config.update("jax_compilation_cache_dir", str(d))
        if ENV_MIN_COMPILE_SECS not in os.environ:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
    return d
