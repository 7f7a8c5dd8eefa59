"""Median time of the serving loop's iterations of one kind, in ms, from
the program's own spans inside the window (`obs/trace.py`, host clock).

`{"name": "tick_median", "which": "decode_only" | "with_prefill"}`. An
iteration is what the loop does between two `tick_prep` spans. A decode tick
ends in a host fetch of the sampled tokens, so its span covers the device's
work; a prefill tick only enqueues unless a prompt finishes in it, and the
device's work on it is waited for in the decode tick that follows. So an
iteration `with_prefill` is timed as its prefill span plus its decode span,
and one with a prefill tick and no decode tick (nothing to wait on) is left
out. `decode_only` iterations have a decode tick and no prefill tick.
"""

from benchmark.stats import median


def read(run, which):
    if which not in ("decode_only", "with_prefill"):
        raise ValueError(f"tick_median: unknown kind {which!r}")
    times, pre, dec = [], None, None

    def close():
        if dec is not None and pre is None and which == "decode_only":
            times.append(dec)
        if dec is not None and pre is not None and which == "with_prefill":
            times.append(pre + dec)

    for name, _t0, dur, _tid, _attrs in sorted(run.spans,
                                               key=lambda ev: ev[1]):
        if name == "tick_prep":
            close()
            pre = dec = None
        elif name == "prefill_tick":
            pre = dur
        elif name == "decode_tick":
            dec = dur
    close()
    if not times:
        return None
    return median(times) / 1e6
