"""One place that decides where JAX's persistent compilation cache lives,
and what the train step's entry in it is keyed by (`scopes_in_key`).

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

import contextlib
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


@contextlib.contextmanager
def scopes_in_key():
    """Programs compiled inside are keyed, in the persistent cache, by
    their operations AND their instructions' name stacks, so by the
    `jax.named_scope`s the program opened; this thread only.

    JAX's default key leaves all metadata out: a tree whose operations
    equal an older tree's is handed that tree's executable, with that
    tree's scopes and its names for unnamed kernel calls, and
    `bps.get_step_scopes()`, which reads the executable that ran, would
    describe those.  JAX's other key takes ALL metadata in, source lines
    among it, and a comment added to a file a step is traced through
    would compile the step anew.  So the tracebacks are left out of what
    is lowered here (`jax_traceback_in_locations_limit` 0: the step's
    instructions then carry their `op_name` and no source location, the
    `stack_frame_id` a profile's source view reads), and the rest is the
    key: a renamed scope or a changed operation misses, a moved line
    hits.  `build_train_step` compiles the train step so, and nothing
    else is."""
    global _key_states
    if _key_states is None:
        try:    # thread-local context managers; JAX exports the values only
            from jax._src import config
            _key_states = (config.compilation_cache_include_metadata_in_key,
                           config.traceback_in_locations_limit)
        except (ImportError, AttributeError):
            _key_states = ()        # a JAX without them: its own key
    if not _key_states:
        yield
        return
    metadata_in_key, traceback_limit = _key_states
    with metadata_in_key(True), traceback_limit(0):
        yield


_key_states = None     # the two config states, () where JAX has none


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
