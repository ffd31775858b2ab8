"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it on its own and
nothing here sets another directory. Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` (git-ignored). The path is part of what makes
a later process find an entry, so it is never built from a temp name, a PID
or the time.

Only entry points call :func:`use_compile_cache` (``chip_smoke.py``,
``repro.launch.train``/``serve``, ``benchmarks/run.py``) — never the library
on import, so tests and ahead-of-time compiles for a described chip do not
write programs that they could not read back.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the env var, else the checkout path."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return cache_dir()
