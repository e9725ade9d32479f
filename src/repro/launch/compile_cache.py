"""JAX's persistent compilation cache, kept at one fixed place.

``use_compile_cache()`` is called once, before the first compile, by every
entry point that runs on the chip (``chip_smoke.py``, ``repro.launch.train``).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is changed.  Otherwise the cache goes to
``<repo>/.jax_cache``: a fixed path, because the directory is part of what
a later run looks up, so a path built from a temp name, a pid or the time
would never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


class CacheCounts:
    """Persistent-cache hits and misses seen since it was registered."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_) -> None:
        name = self._EVENTS.get(event)
        if name:
            setattr(self, name, getattr(self, name) + 1)


def use_compile_cache() -> CacheCounts:
    """Point JAX's persistent cache at its directory; the returned counter
    follows the cache's hits and misses from here on."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    counts = CacheCounts(path)
    jax.monitoring.register_event_listener(counts)
    return counts
