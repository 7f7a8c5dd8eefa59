"""Span recorder — thread-aware, monotonic-clock tracing of the program's
host loops ("fftrace"): the serving tick loop, `fit()`'s training loop
(epoch, data_wait, batch_put, train_step, checkpoint_save, ...) and
whatever else opens an `obs.span`.

Design constraints, in order:

  1. TRUE NO-OP WHEN DISABLED. `obs.span(name)` returns one shared
     `_NULL_SPAN` singleton when no recorder is installed: no object is
     allocated per call, `with` enter/exit touch nothing, and the span
     is falsy so call sites guard their attribute computation
     (`if sp: sp.set(live=...)`) — the attrs dict is never even built.
     A decode tick or a training step pays one module-global load + an
     `is None` test a site.
  2. One clock. Spans stamp `time.monotonic_ns()`; request lifecycle
     events convert the `time.monotonic()` stamps _GenRequest already
     carries — same clock, so tick spans and request tracks line up in
     Perfetto without skew correction.
  3. Correlate with device traces. When enabled (and jax is importable)
     each span also enters `jax.profiler.TraceAnnotation(name)`, so a
     jax-profiler/XLA capture taken over the same window carries the
     host span names alongside the `jax.named_scope` Node.stable_key()
     metadata the executor stamps into HLO (see analysis/hloaudit.py) —
     one vocabulary from scheduler tick down to fused kernel.
  4. Links. Every live span carries an `id` (recorder counter) and a
     `parent` (the id of the span open on the same thread when it
     started, else None) in its attrs, so a reader computes self time
     (duration minus its children's) and walks from a leaf to its tick
     without guessing from intervals. The event tuple stays
     `(name, t0_ns, dur_ns, tid, attrs)`.
  5. One clock with the device trace. `beacon()` emits, at most every
     `BEACON_NS`, a zero-length TraceAnnotation named
     `ffclock:<time.monotonic_ns()>` plus an `ffclock` instant event.
     The profiler stamps the annotation on ITS clock, so a reader of
     the xplane host plane recovers (profiler clock - monotonic clock)
     from the names alone and lays any span, with its attributes, on
     the device timeline.

Export is Chrome-trace/Perfetto `trace_event` JSON: tick-phase spans as
complete ("X") events on their thread's track, per-request lifecycle as
queued/prefill/decode "X" events on one synthetic track per request
(pid 2), thread/process names as "M" metadata events.
"""

from __future__ import annotations

import collections
import gzip
import itertools
import json
import threading
import time
from typing import Deque, Dict, List, Optional

from flexflow_tpu.obs.ledger import TickLedger


class _NullSpan:
    """Falsy no-op span: the disabled-path singleton."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs):
        return None


NULL_SPAN = _NullSpan()

BEACON_NAME = "ffclock"
BEACON_NS = 250_000_000     # at most one clock beacon per 250 ms


class Span:
    """One live span; created only when a recorder is installed."""

    __slots__ = ("_rec", "name", "attrs", "_t0", "_tid", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str):
        self._rec = rec
        self.name = name
        self.attrs: Dict = {"id": next(rec._ids), "parent": None}
        self._t0 = 0
        self._tid = 0
        self._ann = None

    def __bool__(self):
        return True

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        self._tid = threading.get_ident()
        stack = self._rec._open_spans()
        if stack:
            self.attrs["parent"] = stack[-1]
        stack.append(self.attrs["id"])
        ann_cls = self._rec._annotation
        if ann_cls is not None:
            try:
                self._ann = ann_cls(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        # pop down to this span: one left open by an exception between a
        # manual __enter__/__exit__ pair must not adopt later spans
        stack = self._rec._open_spans()
        me = self.attrs["id"]
        while stack and stack.pop() != me:
            pass
        self._rec._finish(self.name, self._t0, t1 - self._t0, self._tid,
                          self.attrs)
        return False


class _Ring(collections.deque):
    """The events' ring. Iterating it walks a snapshot taken in one C
    call: a span that was open on another thread when `obs.disable()`
    returned (a server's loop idles in 1 ms spans) still closes into the
    ring, and a plain deque then raises "mutated during iteration" at a
    reader that walks `recorder.events` itself."""

    def __iter__(self):
        return iter(list(super().__iter__()))


class TraceRecorder:
    """Collects span events in memory, owns the TickLedger, and exports
    Chrome-trace JSON. The events are a RING of `max_events`: once full,
    each new event pushes out the oldest and `dropped` counts it — a
    flight recorder keeps what happened last (an idle loop records three
    events a millisecond, so keeping the FIRST 200k left a server that
    idled a minute, or warmed up cold, with no span of its traffic).
    Appends happen from the scheduler thread while readers may export
    from another — all mutation is deque.append / int adds, safe under
    the GIL, and a reader iterates a snapshot (`_Ring`)."""

    def __init__(self, max_events: int = 200_000,
                 annotate_device: bool = True):
        self.max_events = int(max_events)
        # (name, ts_ns, dur_ns, tid, attrs) complete events, newest kept
        self.events: Deque[tuple] = _Ring(maxlen=self.max_events)
        self.dropped = 0
        # (rid, label, submit_ns, admit_ns, first_ns, done_ns, attrs)
        self.requests: List[tuple] = []
        self._req_seq = 0
        self.ledger = TickLedger()
        self.t0_ns = time.monotonic_ns()
        self._ids = itertools.count(1)      # next() is atomic under the GIL
        self._tls = threading.local()       # per-thread stack of open ids
        self._beacon_ns = 0
        self._annotation = None
        if annotate_device:
            try:
                import jax

                self._annotation = jax.profiler.TraceAnnotation
            except Exception:
                self._annotation = None

    # -- recording -------------------------------------------------------

    def span(self, name: str) -> Span:
        return Span(self, name)

    def _open_spans(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _finish(self, name, t0, dur, tid, attrs):
        if len(self.events) >= self.max_events:
            self.dropped += 1       # the append below pushes the oldest out
        self.events.append((name, t0, dur, tid, attrs))

    def instant(self, name: str, **attrs):
        self._finish(name, time.monotonic_ns(), 0, threading.get_ident(),
                     attrs or None)

    def beacon(self) -> None:
        """Tie this recorder's clock to the profiler's, at most every
        BEACON_NS: a zero-length annotation whose NAME carries the
        monotonic stamp taken just before it opens (the profiler stamps
        its start on its own clock), and an instant event with the same
        stamp for readers of the span list."""
        stamp = time.monotonic_ns()
        if stamp - self._beacon_ns < BEACON_NS:
            return
        self._beacon_ns = stamp
        ann_cls = self._annotation
        if ann_cls is not None:
            try:
                with ann_cls(f"{BEACON_NAME}:{stamp}"):
                    pass
            except Exception:
                pass
        self.instant(BEACON_NAME, stamp=stamp)

    def record_request(self, submit_t: float, admit_t: Optional[float],
                       first_token_t: Optional[float], done_t: float,
                       label: str = "", attrs: Optional[Dict] = None
                       ) -> int:
        """One completed request's lifecycle from the monotonic-seconds
        stamps _GenRequest carries: queued [submit→admit], prefill
        [admit→first token], decode [first token→done]. Missing stamps
        collapse their phase to zero width at the next known edge."""
        self._req_seq += 1
        rid = self._req_seq
        to_ns = lambda s: int(s * 1e9)  # noqa: E731 — same monotonic clock
        admit = admit_t if admit_t is not None else done_t
        first = first_token_t if first_token_t is not None else done_t
        self.requests.append((rid, label or f"req {rid}", to_ns(submit_t),
                              to_ns(admit), to_ns(first), to_ns(done_t),
                              attrs))
        return rid

    # -- export ----------------------------------------------------------

    @staticmethod
    def _us(ns: int) -> float:
        return ns / 1e3

    def chrome_trace(self) -> Dict:
        """`trace_event` JSON: pid 1 = tick loop threads, pid 2 = one
        synthetic track per request. Loads in chrome://tracing and
        https://ui.perfetto.dev unmodified."""
        ev: List[Dict] = [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "fftrace: tick loop"}},
            {"ph": "M", "name": "process_name", "pid": 2,
             "args": {"name": "fftrace: requests"}},
        ]
        tids = set()
        for name, t0, dur, tid, attrs in self.events:
            tids.add(tid)
            e = {"name": name, "ph": "X", "cat": "tick", "pid": 1,
                 "tid": tid, "ts": self._us(t0 - self.t0_ns),
                 "dur": self._us(dur)}
            if attrs:
                e["args"] = attrs
            ev.append(e)
        for tid in sorted(tids):
            ev.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": tid, "args": {"name": f"loop thread {tid}"}})
        for rid, label, sub, adm, first, done, attrs in list(self.requests):
            ev.append({"ph": "M", "name": "thread_name", "pid": 2,
                       "tid": rid, "args": {"name": label}})
            for phase, a, b in (("queued", sub, adm),
                                ("prefill", adm, first),
                                ("decode", first, done)):
                e = {"name": phase, "ph": "X", "cat": "request", "pid": 2,
                     "tid": rid, "ts": self._us(a - self.t0_ns),
                     "dur": self._us(max(b - a, 0))}
                if phase == "decode" and attrs:
                    e["args"] = attrs
                ev.append(e)
        ev.sort(key=lambda e: (e.get("ts", -1.0), e["pid"]))
        return {"traceEvents": ev, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export_chrome_trace(self, path: str) -> str:
        """Write the trace JSON (gzipped when `path` ends in .gz)."""
        doc = self.chrome_trace()
        if path.endswith(".gz"):
            with gzip.open(path, "wt") as f:
                json.dump(doc, f)
        else:
            with open(path, "w") as f:
                json.dump(doc, f)
        return path
