"""One place that decides where JAX's persistent compilation cache lives.

For the scripts that run on the chip (chip_smoke.py, benchmark/run.py,
tools/reference_check.py, tools/afmoe_drift.py).  The library itself
(`bps.init`) sets no cache.

The rule: where `JAX_COMPILATION_CACHE_DIR` is set, the cache was placed
from outside — JAX reads the variable itself and nothing is set in code.
Otherwise the cache goes to ONE fixed directory inside the checkout
(gitignored).  The path is part of the cache's key, so a directory built
from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache is (or would be) in.  Touches no JAX: a
    parent that only starts children puts this in their environment."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Call before the first compile."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class HitCounter:
    """Counts this process's persistent-cache hits and misses from JAX's
    own monitoring events, so a script can report whether its compiles
    were warm."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1
