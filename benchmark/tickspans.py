"""What the readers of the serving tick's phase spans share (the spans are
`flexflow_tpu/paged/scheduler.py`'s, listed in `docs/observability.md`): the
loop's iterations, the profiler's planes read once a run, and the offset
between the program's span clock and the profiler's.

A program without the phase spans (a parent commit) gives every reader
here nothing to read: each returns None and its metric is left out.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmark import xplane
from benchmark.harness import Run, log
from benchmark.stats import median

BEACON = "ffclock:"
BEACON_MIN = 3
BEACON_RESIDUAL_NS = 50_000.0


def has_phases(events) -> bool:
    """Whether the program that wrote these events (spans, or a host
    plane's events: the name comes first in both) marks the phases.
    `commit` alone does not say so: the speculative path always had one."""
    return any(ev[0] == "launch_dispatch" for ev in events)


def iterations(spans) -> List[Dict]:
    """The loop's iterations inside the window, split at `tick_prep` starts
    as `readers/tick_median.py` splits them: {"t0", "wall_ns" (to the next
    `tick_prep`, None for the last), "prefill", "decode" (whether it held
    such a tick), "fetch_ns" (summed `fetch` spans)}."""
    out: List[Dict] = []
    cur = None
    for name, t0, dur, _tid, _attrs in sorted(spans, key=lambda ev: ev[1]):
        if name == "tick_prep":
            if cur is not None:
                cur["wall_ns"] = t0 - cur["t0"]
            cur = {"t0": t0, "wall_ns": None, "prefill": False,
                   "decode": False, "fetch_ns": 0}
            out.append(cur)
        elif cur is None:
            continue
        elif name == "prefill_tick":
            cur["prefill"] = True
        elif name == "decode_tick":
            cur["decode"] = True
        elif name == "fetch":
            cur["fetch_ns"] += dur
    return out


def planes(run: Run) -> Optional[Dict]:
    """The run's xplane file as `xplane.read_planes` gives it, read once
    and kept on `run.extras`; None where no trace was written."""
    if "planes" not in run.extras:
        path = xplane.find_xplane(run.trace_dir())
        run.extras["planes"] = xplane.read_planes(path) if path else None
    return run.extras["planes"]


def beacon_offset(host: Dict[str, list]) -> Optional[Tuple[float, float, int]]:
    """(offset_ns, residual_ns, beacons): what to add to a span's
    `time.monotonic_ns` stamp to get the profiler's clock. Each beacon on
    the host plane is named `ffclock:<stamp>`, the program's clock read
    just before the annotation opened; the offset is the median of
    (annotation start - stamp), the residual the farthest beacon from it.
    None with fewer than BEACON_MIN beacons, or when more than a quarter of
    them lie over BEACON_RESIDUAL_NS from the median: the clocks are then
    not tied well enough to lay one launch beside its kernels. (A single
    beacon whose thread lost the processor between the stamp and the
    annotation is late by itself and does not move a median: one of 18 was
    25 us late in one of seven runs on the chip, PR 24.)"""
    diffs = []
    for events in host.values():
        for name, start, _dur in events:
            if name.startswith(BEACON):
                try:
                    diffs.append(start - int(name[len(BEACON):]))
                except ValueError:
                    continue
    if len(diffs) < BEACON_MIN:
        log(f"clock beacons: {len(diffs)} found, {BEACON_MIN} needed")
        return None
    offset = median(diffs)
    residual = max(abs(d - offset) for d in diffs)
    far = sum(1 for d in diffs if abs(d - offset) > BEACON_RESIDUAL_NS)
    log(f"clock beacons: {len(diffs)}, offset {offset:.0f} ns, residual "
        f"{residual:.0f} ns, {far} over {BEACON_RESIDUAL_NS:.0f} ns away")
    if 4 * far > len(diffs):
        return None
    return offset, residual, len(diffs)
