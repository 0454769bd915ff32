"""Compile accounting and the persistent compile cache.

``CompileLog`` listens to JAX's own monitoring event for every backend
compile (``/jax/core/compile/backend_compile_duration``).
``use_compile_cache`` keeps the
cache at a fixed path inside the checkout, or where
``JAX_COMPILATION_CACHE_DIR`` says: the path is part of the cache's key.
"""
from __future__ import annotations

import os

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache(root: str) -> str:
    """Turn on the persistent compile cache before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileLog:
    """Running totals of backend compiles and their seconds."""

    def __init__(self, monitoring):
        self.count = 0
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.secs += secs

    def snapshot(self):
        return self.count, self.secs
