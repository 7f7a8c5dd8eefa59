"""fftrace — structured tracing + metrics for the program's host loops:
the serving tick loop and the training loop (`fit()`), with the names the
executor stamps into its compiled steps (obs.scopes) for the device side.

Layers with different overhead budgets:

  * `MetricsRegistry` (obs.metrics): counters/gauges/fixed-bucket
    histograms. Always on — every generation server owns one and feeds
    both the JSON metrics endpoint and the Prometheus text endpoint.
    An observe() is a bisect + two adds.
  * Span recorder + TickLedger (obs.trace / obs.ledger): opt-in via
    `obs.enable()`. When disabled, `obs.span(name)` returns a shared
    falsy singleton — zero allocations on the tick path and in
    `fit()`'s loop (the disabled-overhead guards in tests/test_obs.py
    and tests/test_fit_spans.py hold this to account).
  * Step scopes (obs.scopes): `forward` / `optimizer` / `step_metrics`
    around the graph nodes' keys, stamped by the executor into the train
    and eval steps; `classify` is the one reader of the resulting JAX
    name stacks (hloaudit, the benchmark's device-trace reader). Named
    scopes cost nothing at run time.
  * Request log + SLO monitor (obs.reqlog / obs.slo): a bounded
    flight recorder of one record per COMPLETED request (cheap enough
    to leave on in production; `request_log(0)` is the same falsy
    no-op discipline as span), the replay substrate for `servesearch
    search --replay` and `fftrace replay`, and the sliding-window SLO
    judge whose breach events dump the recorder state to disk.

Usage on a hot path:

    from flexflow_tpu import obs
    ...
    with obs.span("decode_tick") as sp:
        if sp:  # only build the attrs dict when someone is recording
            sp.set(live=len(live), width=T)
        ...

Calibration (see obs.calibrate and tools/fftrace.py):

    obs.enable()
    ... serve traffic ...
    obs.recorder().export_chrome_trace("trace.json")   # Perfetto
    led = obs.ledger(); stamp_ledger_meta(led, ff); led.save("ledger.json")
    # fftrace calibrate ledger.json -> per-tick-shape scale factors
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.obs.compile_tracker import CompileTracker
from flexflow_tpu.obs.ledger import TickLedger, shape_key
from flexflow_tpu.obs.metrics import (
    COUNT_BUCKETS,
    RATIO_BUCKETS,
    TIME_BUCKETS_S,
    Histogram,
    MetricsRegistry,
    flatten_scalars,
)
from flexflow_tpu.obs.reqlog import (
    NULL_REQLOG,
    BoundedRing,
    RequestLog,
    dump_jsonl,
    load_jsonl,
    request_log,
)
from flexflow_tpu.obs.slo import SLOMonitor, SLOTarget
from flexflow_tpu.obs.trace import NULL_SPAN, Span, TraceRecorder

_recorder: Optional[TraceRecorder] = None


def enable(max_events: int = 200_000,
           annotate_device: bool = True) -> TraceRecorder:
    """Install a fresh TraceRecorder (replacing any previous one) and
    return it. Spans and ledger recording start immediately."""
    global _recorder
    _recorder = TraceRecorder(max_events=max_events,
                              annotate_device=annotate_device)
    return _recorder


def disable() -> Optional[TraceRecorder]:
    """Stop recording; returns the recorder so its events/ledger can
    still be exported after the fact."""
    global _recorder
    rec, _recorder = _recorder, None
    return rec


def enabled() -> bool:
    return _recorder is not None


def recorder() -> Optional[TraceRecorder]:
    return _recorder


def ledger() -> Optional[TickLedger]:
    return _recorder.ledger if _recorder is not None else None


def span(name: str):
    """A live Span when enabled, else the falsy no-op singleton."""
    rec = _recorder
    if rec is None:
        return NULL_SPAN
    return Span(rec, name)


def beacon() -> None:
    """Emit a clock beacon (TraceRecorder.beacon) when enabled; one
    global load and an `is None` test when not."""
    rec = _recorder
    if rec is not None:
        rec.beacon()


__all__ = [
    "COUNT_BUCKETS",
    "BoundedRing",
    "CompileTracker",
    "Histogram",
    "MetricsRegistry",
    "NULL_REQLOG",
    "NULL_SPAN",
    "RATIO_BUCKETS",
    "RequestLog",
    "SLOMonitor",
    "SLOTarget",
    "Span",
    "TIME_BUCKETS_S",
    "TickLedger",
    "TraceRecorder",
    "beacon",
    "disable",
    "dump_jsonl",
    "enable",
    "enabled",
    "flatten_scalars",
    "ledger",
    "load_jsonl",
    "recorder",
    "request_log",
    "shape_key",
    "span",
]
