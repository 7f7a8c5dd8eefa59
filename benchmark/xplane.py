"""From the profiler's trace to numbers: the reduction every PR shares.

`jax.profiler` writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but JAX. A TPU chip is a
plane named `/device:TPU:<n>`; its line `XLA Ops` carries one event per
operation the chip ran (start and duration in nanoseconds, on the clock the
host planes use too), `XLA Modules` one per program. Host threads are lines
of the plane `/host:CPU`, where `obs/trace.py`'s spans appear by name
because each also enters a `jax.profiler.TraceAnnotation`.

`reduce_events` is pure: tests feed it the events of a small trace recorded
on the chip (`tests/benchmark/data/`), so the arithmetic is checked without
one.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.stats import union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# collective operations as XLA names them on a TPU
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)

SHORT_GAP_NS = 20_000

Event = Tuple[str, float, float]      # name, start_ns, duration_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


KERNEL_MARK = " [tpu_custom_call]"


def short_name(name: str) -> str:
    """The XLA Ops line names an event by its whole HLO line (`%fusion.3 =
    bf16[...] fusion(...)`); the instruction's name is enough, with a mark
    where the line is a Pallas kernel's custom call, because under
    `shard_map` a kernel's instruction is named after the map and not
    after the kernel."""
    if not name.startswith("%"):
        return name
    short = name.split(" = ", 1)[0].lstrip("%")
    return short + KERNEL_MARK if "tpu_custom_call" in name else short


def read_planes(path: str) -> Dict:
    """{"devices": {chip: [Event]}, "host": {thread: [Event]}} from an
    xplane file. Device events are those of the `XLA Ops` line."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (short_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)) for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events]
                if evs:
                    host.setdefault(line.name, []).extend(evs)
    return {"devices": devices, "host": host}


def _top(pairs: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(pairs.items(),
                                      key=lambda kv: -kv[1])[:n]]


def _by_family(by_name: Dict[str, float]) -> Dict[str, float]:
    """`fusion.12` and `fusion.7` are one family, `fusion`."""
    out: Dict[str, float] = {}
    for name, secs in by_name.items():
        fam = re.sub(r"\.\d+(?= \[|$)", "", name)
        out[fam] = out.get(fam, 0.0) + secs
    return out


def _covering_span(spans: Sequence[Event], t: float,
                   names: Optional[set]) -> str:
    """The innermost host span of `names` open at time t."""
    best, best_dur = "no span", float("inf")
    for name, s, d in spans:
        if s <= t <= s + d and d < best_dur and (
                names is None or name in names):
            best, best_dur = name, d
    return best


def reduce_events(devices: Dict[int, List[Event]],
                  host: Dict[str, List[Event]],
                  span_names: Optional[Iterable[str]] = None,
                  window: Optional[Tuple[float, float]] = None) -> Dict:
    """Busy and idle time, time by operation, collective time and the
    longest idle gaps by what the host was doing in them.

    `window` is (start_ns, end_ns) on the trace's clock; by default it runs
    from the first device event's start to the last one's end. Seconds
    throughout. `per_chip[chip]["by_name"]` holds summed durations of
    every operation name, for the readers' regular expressions; nested
    operations (a fusion inside a while loop) both appear there, so a
    pattern should name leaves. `busy_s` is a union and counts each
    instant once."""
    names = set(span_names) if span_names is not None else None
    if not devices or not any(devices.values()):
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0, "per_chip": {},
                "device_ops": [], "idle_gaps": []}
    if window is None:
        starts = [e[1] for evs in devices.values() for e in evs]
        ends = [e[1] + e[2] for evs in devices.values() for e in evs]
        window = (min(starts), max(ends))
    w0, w1 = window
    window_s = (w1 - w0) / 1e9
    host_spans = [e for evs in host.values() for e in evs
                  if names is None or e[0] in names]
    per_chip: Dict[int, Dict] = {}
    for chip, evs in sorted(devices.items()):
        clipped = []
        by_name: Dict[str, float] = {}
        coll = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
            if COLLECTIVE.search(name):
                coll.append((a, b))
        per_chip[chip] = {
            "busy_s": union_length(clipped) / 1e9,
            "collective_s": union_length(coll) / 1e9,
            "by_name": by_name,
            "events": len(clipped),
        }
    # idle gaps of the first chip, by the host span open in their middle
    first = min(devices)
    ivs = sorted((max(s, w0), min(s + d, w1)) for _n, s, d in devices[first]
                 if min(s + d, w1) > max(s, w0))
    gaps: Dict[str, float] = {}

    def charge(a: float, b: float) -> None:
        # a gap shorter than SHORT_GAP_NS is the chip's own turn-around
        # between two operations, not something the host did
        who = (f"gaps under {SHORT_GAP_NS // 1000} us"
               if b - a < SHORT_GAP_NS
               else _covering_span(host_spans, (a + b) / 2.0, names))
        gaps[who] = gaps.get(who, 0.0) + (b - a) / 1e9

    edge = w0
    for a, b in ivs:
        if a > edge:
            charge(edge, a)
        edge = max(edge, b)
    if w1 > edge:
        charge(edge, w1)
    n = len(per_chip)
    return {
        "chips": n,
        "busy_s": sum(c["busy_s"] for c in per_chip.values()) / n,
        "window_s": window_s,
        "per_chip": per_chip,
        "device_ops": _top(_by_family(per_chip[first]["by_name"])),
        "idle_gaps": _top(gaps),
    }


def time_matching(reduction: Dict, pattern: str, chip: Optional[int] = None
                  ) -> float:
    """Seconds of device time in operations whose name matches `pattern`,
    on one chip (default: the first)."""
    if not reduction["per_chip"]:
        return 0.0
    if chip is None:
        chip = min(reduction["per_chip"])
    rx = re.compile(pattern)
    return sum(v for k, v in reduction["per_chip"][chip]["by_name"].items()
               if rx.search(k))
