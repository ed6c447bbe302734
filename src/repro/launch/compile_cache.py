"""JAX's persistent compilation cache, and a clock for time spent compiling.

``enable()`` points the cache at one fixed directory. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here; otherwise the cache goes to ``<checkout>/.jax_cache``. The
path is part of the cache key, so it never depends on a temp dir, pid or
time — a second run of the same program finds what the first one wrote.

``CompileClock`` sums JAX's own event for getting an executable (an XLA
compile or a persistent-cache load), so a launcher can report per step how
much of its wall time went to compiling (tracing and lowering are not
counted).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX times ``compile_or_get_cached`` under this one event, a cache load
# included; its cache-retrieval event fires inside that span, so adding it
# would count a hit twice. Tracing is left out: JAX reports a trace event
# for every nested function, so those events overlap.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


class CompileClock:
    """Running total of compile seconds in this process."""

    def __init__(self):
        self.seconds = 0.0
        self._installed = False

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration

    def start(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self._installed = True
        return self

    def stop(self) -> None:
        if self._installed:
            jax.monitoring.unregister_event_duration_listener(self._on_duration)
            self._installed = False
