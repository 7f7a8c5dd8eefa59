"""The device's idle time by the host phase it fell under, as a percentage
of the traced window, chip 0 (device trace; the spans reach the profiler's
host plane by name through `TraceAnnotation`, so no clock is joined here).

`{"name": "idle_by_span", "group": "launch" | "fetch" | "commit"}`. Every
idle gap of 20 us or more is SHARED OUT over the innermost tick span open at
each instant of it, all twelve span names known (the six tick spans and the
six phases). `xplane.reduce_events` gives a whole gap to the span open at its
middle; that is right for a breakdown by tick but not by phase: a decode
tick leaves the chip ONE gap, from its last operation (inside `fetch`,
before the host has the result) through `commit` and `tick_prep` to the next
`step` reaching it (inside `launch_dispatch`), and its middle falls in
`fetch` or in `launch_h2d` by a fraction of a millisecond. The groups:

    launch   launch_build, launch_h2d, launch_dispatch, sample
    fetch    fetch: the device is done and the host does not have it yet
    commit   commit, tick_prep, admit_pending, defrag
    idle_wait, "no span", "gaps under 20 us": each its own
    tick     decode_tick or prefill_tick ITSELF innermost: the unexplained
             remainder. Over 2 % of the window, a phase lacks its span.

The groups partition the idle time of the window `reduce_events` uses (first
device operation to last), so together they are the result line's idle share.
"""

from benchmark import tickspans, xplane
from benchmark.harness import log

GROUPS = {"launch_build": "launch", "launch_h2d": "launch",
          "launch_dispatch": "launch", "sample": "launch",
          "fetch": "fetch",
          "commit": "commit", "tick_prep": "commit",
          "admit_pending": "commit", "defrag": "commit",
          "idle_wait": "idle_wait",
          "prefill_tick": "tick", "decode_tick": "tick"}
NO_SPAN = "no span"
SHORT = f"gaps under {xplane.SHORT_GAP_NS // 1000} us"
REMAINDER_MAX = 2.0     # % of the window


def innermost_segments(spans):
    """[(start, end, name)] in time order: which of `spans` (name, start,
    duration) is innermost, that is shortest among those open, over each
    stretch of time where any is open."""
    edges = []
    for i, (_name, s, d) in enumerate(spans):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))     # closes sort before opens at a tie
    edges.sort()
    open_, out, at = set(), [], None
    for t, opens, i in edges:
        if open_ and t > at:
            inner = min(open_, key=lambda j: spans[j][2])
            out.append((at, t, spans[inner][0]))
        if opens:
            open_.add(i)
        else:
            open_.discard(i)
        at = t
    return out


def idle_by_name(device_events, spans):
    """Seconds of idle time of one chip's events by the innermost span of
    `spans` open during it; gaps under `xplane.SHORT_GAP_NS` and time under
    no span are their own names."""
    ivs = sorted((s, s + d) for _n, s, d in device_events)
    gaps, edge = [], ivs[0][0]
    for a, b in ivs:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    segs = innermost_segments(spans)
    out, k = {}, 0

    def add(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns / 1e9

    for a, b in gaps:
        if b - a < xplane.SHORT_GAP_NS:
            add(SHORT, b - a)
            continue
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        j, covered = k, 0.0
        while j < len(segs) and segs[j][0] < b:
            part = min(b, segs[j][1]) - max(a, segs[j][0])
            add(segs[j][2], part)
            covered += max(part, 0.0)
            j += 1
        add(NO_SPAN, (b - a) - covered)
    return out


def shares(run):
    """{group: % of the traced window}, computed once a run."""
    if "idle_by_span" in run.extras:
        return run.extras["idle_by_span"]
    run.extras["idle_by_span"] = None
    planes = tickspans.planes(run)
    if not planes or not planes["devices"]:
        return None
    host = planes["host"]
    if not any(tickspans.has_phases(evs) for evs in host.values()):
        return None
    chip0 = planes["devices"][min(planes["devices"])]
    spans = [ev for evs in host.values() for ev in evs if ev[0] in GROUPS]
    by_name = idle_by_name(chip0, spans)
    window = (max(s + d for _n, s, d in chip0)
              - min(s for _n, s, _d in chip0)) / 1e9
    if window <= 0.0:
        return None
    out = {}
    for name, secs in by_name.items():
        group = GROUPS.get(name, name)
        out[group] = out.get(group, 0.0) + 100.0 * secs / window
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    log(f"idle by host phase, % of the traced window ({window:.3f} s): "
        + ", ".join(f"{k} {v:.2f}" for k, v in sorted(
            out.items(), key=lambda kv: -kv[1]))
        + f"; together {sum(out.values()):.2f}")
    log("idle by innermost span, ms: "
        + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in ranked))
    if out.get("tick", 0.0) > REMAINDER_MAX:
        log(f"idle under a tick span and no phase is {out['tick']:.2f} % "
            f"of the window, over {REMAINDER_MAX} %: a phase lacks a span")
    run.extras["idle_by_span"] = out
    return out


def read(run, group):
    if group not in ("launch", "fetch", "commit"):
        raise ValueError(f"idle_by_span: unknown group {group!r}")
    out = shares(run)
    if out is None:
        return None
    return out.get(group, 0.0)
