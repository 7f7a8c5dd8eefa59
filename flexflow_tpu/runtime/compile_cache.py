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


def keyed_on_metadata():
    """A context in which a program is compiled under a cache key that
    holds its metadata. jax strips locations from a module before it
    hashes it, and a `jax.named_scope` changes nothing but locations: a
    cache warmed by a checkout without a scope would hand one with it the
    old executable, whose `op_name`s lack the scope, and a reader of the
    device trace (obs/scopes.py) would see an unscoped step on a warm
    cache and a scoped one on a cold. The training and evaluation steps
    enter this around their calls (`Executor._TracedStep`); the serving
    programs do not, and keep their keys."""
    from jax._src import config

    return config.compilation_cache_include_metadata_in_key(True)


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
