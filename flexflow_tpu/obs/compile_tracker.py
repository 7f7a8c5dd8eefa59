"""Compile-event tracker — the runtime arm of the shapecheck pass
(docs/analysis.md "shapecheck", docs/observability.md "Compile events").

Every distinct input shape hitting a `jax.jit` entry point costs an XLA
compilation. The static arm (analysis/shapecheck.py) enumerates the
closed catalog of reachable launch shapes per served config; this
module OBSERVES the compilations that actually happen, so the two can
be diffed:

  * `CompileTracker.wrap(entry, fn, sig_fn)` wraps a jitted callable.
    Real XLA compiles are detected through jax's monitoring events
    (`/jax/core/compile/*` durations fire synchronously on the calling
    thread, so a thread-local frame attributes them to the wrapped call
    in flight); each compiling call records {entry, shape, seconds,
    steady_state} with `seconds` the summed trace+lower+backend-compile
    time. The jit dispatch cache also keys on argument COMMITTEDNESS
    (device-bound jit outputs vs fresh host uploads), so it grows new
    entries that reuse an existing lowering — those cost ~ms, compile
    nothing, and are deliberately NOT events. When the monitoring hook
    is unavailable the fallback is the jit wrapper's own cache-size
    delta (`fn._cache_size()`), or a seen-signature set below that;
    there `seconds` wall-times the missing call (an upper bound that
    includes the first execution — the conservative direction for TTFT
    accounting).
  * `mark_steady_state()` flips the phase bit after warmup: every event
    recorded afterwards increments the `steady_state_recompiles` gauge
    — the number the CI soundness gate pins at zero.
  * `set_registry(MetricsRegistry)` exports `ff_compile_seconds` (a
    histogram of per-event compile wall time) and the
    `ff_compile_events_total` counter; scoped scalar totals also ride
    the server's metrics() payload alongside the
    `ff_steady_state_recompiles` / `ff_jit_cache_entries` gauges the
    serving layer sets.

The tracker only touches jax lazily (the optional monitoring hook) and
degrades to plain callables: any function works, at one list append
plus one clock read per wrapped call on the hit path.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# thread-local stack of in-flight wrapped calls: jax's monitoring
# listeners fire synchronously on the compiling thread, so the top
# frame is the call any compile event belongs to
_tls = threading.local()
_listener_state = {"installed": None}  # None = not tried yet
# jax.monitoring duration events -> the keys of compile_split()'s dict;
# backend_compile is the XLA compile or, on a persistent-cache hit, the load
_SPLIT_KEYS = {"jaxpr_trace_duration": "trace_s",
               "jaxpr_to_mlir_module_duration": "lower_s",
               "backend_compile_duration": "backend_s"}
_install_lock = threading.Lock()


def _on_duration_event(name: str, seconds: float, **_kw) -> None:
    if not name.startswith("/jax/core/compile/"):
        return
    split = getattr(_tls, "split", None)
    if split is not None:
        key = _SPLIT_KEYS.get(name.rsplit("/", 1)[-1])
        if key is not None:
            split[key] += float(seconds)
    stack = getattr(_tls, "stack", None)
    if not stack:
        return
    frame = stack[-1]
    frame["seconds"] += float(seconds)
    if name.endswith("backend_compile_duration"):
        frame["compiles"] += 1


def _install_listener() -> bool:
    """Register the compile-event listener once per process; False when
    jax cannot be imported (the wrapper then falls back to cache-size
    deltas — the tracker itself works on plain callables)."""
    if _listener_state["installed"] is None:
        with _install_lock:
            if _listener_state["installed"] is None:
                try:
                    import jax.monitoring

                    jax.monitoring.register_event_duration_secs_listener(
                        _on_duration_event)
                    _listener_state["installed"] = True
                except ImportError:
                    _listener_state["installed"] = False
    return _listener_state["installed"]


@contextlib.contextmanager
def compile_split():
    """Seconds jax spends tracing, lowering and backend-compiling (or
    loading from the persistent cache) on THIS thread while the context
    is open, as {"trace_s", "lower_s", "backend_s"}; zeros when the
    monitoring hook is unavailable. For set-up records only (the
    `warm_shape` span): the tick path never opens one."""
    split = dict.fromkeys(_SPLIT_KEYS.values(), 0.0)
    _install_listener()
    outer = getattr(_tls, "split", None)
    _tls.split = split
    try:
        yield split
    finally:
        _tls.split = outer


def _default_sig(args: Sequence[Any]) -> Tuple[int, ...]:
    """Fallback signature: the shape of the first array-like argument."""
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            return tuple(int(x) for x in shape)
    return ()


class _TrackedJit:
    """Transparent wrapper around one jitted entry point. Delegates
    everything (`.lower()`, `.clear_cache()`, ...) to the wrapped
    function — same contract as the executor's _TracedStep shim."""

    __slots__ = ("_fn", "_entry", "_sig_fn", "_tracker", "_seen")

    def __init__(self, tracker: "CompileTracker", entry: str,
                 fn: Callable, sig_fn: Optional[Callable] = None):
        self._tracker = tracker
        self._entry = entry
        self._fn = fn
        self._sig_fn = sig_fn
        self._seen: set = set()

    def _shape(self, args) -> Tuple[int, ...]:
        try:
            return tuple(int(x) for x in (self._sig_fn(args)
                                          if self._sig_fn
                                          else _default_sig(args)))
        except Exception:
            return ()

    def __call__(self, *args, **kwargs):
        if _install_listener():
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            frame = {"compiles": 0, "seconds": 0.0}
            stack.append(frame)
            try:
                out = self._fn(*args, **kwargs)
            finally:
                stack.pop()
            if frame["compiles"]:
                self._tracker.record(self._entry, self._shape(args),
                                     frame["seconds"])
            return out
        cache_size = getattr(self._fn, "_cache_size", None)
        if callable(cache_size):
            before = cache_size()
            t0 = time.monotonic()
            out = self._fn(*args, **kwargs)
            if cache_size() > before:
                self._tracker.record(self._entry, self._shape(args),
                                     time.monotonic() - t0)
            return out
        # no hook at all: first sighting of each canonical signature
        # counts as the compile (an approximation that still catches
        # every shape-space escape, the property the gate pins)
        shape = self._shape(args)
        if shape in self._seen:
            return self._fn(*args, **kwargs)
        t0 = time.monotonic()
        out = self._fn(*args, **kwargs)
        self._seen.add(shape)
        self._tracker.record(self._entry, shape, time.monotonic() - t0)
        return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class CompileTracker:
    """Process-wide (per-Executor) ledger of jit compile events."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: List[Dict] = []
        self._steady = False
        self._registry = None
        self._h_seconds = None
        self._c_events = None

    # -- wiring -----------------------------------------------------------

    def wrap(self, entry: str, fn: Callable,
             sig_fn: Optional[Callable] = None) -> _TrackedJit:
        """Wrap a jitted callable; `sig_fn(args) -> tuple` extracts the
        canonical launch-shape signature (the catalog's coordinate
        system) from one call's arguments."""
        return _TrackedJit(self, entry, fn, sig_fn)

    def set_registry(self, registry) -> None:
        """Bind a MetricsRegistry: subsequent events observe the
        `compile_seconds` histogram and increment the
        `compile_events_total` counter (events recorded before binding
        ride metrics() snapshots only — counters cannot be back-dated)."""
        with self._lock:
            self._registry = registry
            self._h_seconds = registry.histogram("compile_seconds")
            self._c_events = registry.counter("compile_events_total")

    def mark_steady_state(self) -> None:
        """Warmup is over: every compile event from here on is a
        steady-state recompile — the count the soundness gate pins at
        zero."""
        with self._lock:
            self._steady = True

    def mark_warmup(self) -> None:
        """Re-enter the warmup phase. An executor-owned tracker outlives
        any one server; a new server starting its own warm cycle (the
        common sequential-servers pattern in tests) must not have its
        warm compiles counted as the previous server's steady-state
        recompiles."""
        with self._lock:
            self._steady = False

    # -- recording --------------------------------------------------------

    def record(self, entry: str, shape: Tuple[int, ...],
               seconds: float) -> None:
        with self._lock:
            self._events.append({
                "entry": entry,
                "shape": tuple(int(x) for x in shape),
                "seconds": float(seconds),
                "steady_state": self._steady,
            })
            if self._h_seconds is not None:
                self._h_seconds.observe(float(seconds))
            if self._c_events is not None:
                self._c_events.inc()

    # -- reading ----------------------------------------------------------

    @property
    def in_steady_state(self) -> bool:
        return self._steady

    def observed(self, since: int = 0) -> List[Dict]:
        """Copies of recorded events (from index `since` — a server
        passes its creation-time event count to scope the view to its
        own lifetime) — check_soundness input."""
        with self._lock:
            return [dict(ev) for ev in self._events[since:]]

    def observed_shapes(self) -> Dict[str, set]:
        """entry -> set of observed launch-shape signatures."""
        out: Dict[str, set] = {}
        with self._lock:
            for ev in self._events:
                out.setdefault(ev["entry"], set()).add(ev["shape"])
        return out

    @property
    def compile_events_total(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def compile_seconds_total(self) -> float:
        with self._lock:
            return sum(ev["seconds"] for ev in self._events)

    @property
    def steady_state_recompiles(self) -> int:
        with self._lock:
            return sum(1 for ev in self._events if ev["steady_state"])

    def snapshot(self, since: int = 0) -> Dict:
        """Scalar block for a server's metrics() payload (the /metrics
        endpoint renders *_total names as Prometheus counters). `since`
        scopes the totals to events recorded after that index — a
        server's own lifetime on a shared executor tracker."""
        with self._lock:
            evs = self._events[since:]
            return {
                "compile_events_total": len(evs),
                "compile_seconds_sum": round(
                    sum(ev["seconds"] for ev in evs), 6),
                "steady_state_recompiles": sum(
                    1 for ev in evs if ev["steady_state"]),
            }
