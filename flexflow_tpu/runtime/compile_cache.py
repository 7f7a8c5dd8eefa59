"""Where JAX's persistent compilation cache lives.

Compiling is most of a cold run on the chip, and nothing survives one
chip-tool call except what its processes share on disk. The cache is
placed from OUTSIDE when it can be: if `JAX_COMPILATION_CACHE_DIR` is set,
jax reads it itself and nothing here sets a directory in code. Otherwise the
directory is one fixed path inside the checkout — the path is part of the
cache key's environment, so a directory made from `tempfile`, a pid or the
time would never hit.

Called before the first compile by the entry points that run on the chip
(chip_smoke.py, bench.py's children, `python -m flexflow_tpu`). Not by
tests/conftest.py: the suite compiles what it tests.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CacheCounter:
    """Counts this process's persistent-cache hits and misses from jax's
    monitoring events, so a run can say whether its compile seconds were
    cold or warm."""

    def __init__(self):
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1
