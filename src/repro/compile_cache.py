"""Persistent XLA compilation cache for the entry-point scripts.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` once,
before their first compile; the test suite never does.  The cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads the
variable itself, so it is left in force), and otherwise at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
the cache key, so it never carries a temp name, pid or time.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


@dataclass
class CacheCounter:
    """Where the cache lives and the hits/misses counted since enable."""

    path: str
    hits: int = 0
    misses: int = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1


def enable() -> CacheCounter:
    """Turn the persistent cache on and count its hits and misses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the skim's kernels compile in well under JAX's 1 s default floor;
    # keep every program, small ones included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CacheCounter(path)
    jax.monitoring.register_event_listener(counter._on_event)
    return counter
