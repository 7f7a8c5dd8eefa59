"""Host time of one loop iteration, in ms: median over the iterations of one
kind of (wall time to the next `tick_prep` minus the `fetch` spans inside),
from the program's spans inside the window (host clock).

`{"name": "iteration_host", "which": "decode_only" | "with_prefill"}`, the
kinds of `tick_median`. A `fetch` span is the host blocked on the device's
result, so what is left is everything the host does itself in an iteration:
admission, launch assembly, uploads, dispatches, the commit with its
callbacks, and the loop's own overhead between spans. The device works
through part of it (dispatch is asynchronous), so this is the host's cost,
not the device's idle time: `idle_by_span` reads that.
"""

from benchmark import tickspans
from benchmark.harness import log
from benchmark.stats import median, percentile


def read(run, which):
    if which not in ("decode_only", "with_prefill"):
        raise ValueError(f"iteration_host: unknown kind {which!r}")
    if not any(ev[0] == "fetch" for ev in run.spans):
        return None     # a program that does not mark the wait: no host time
    want_prefill = which == "with_prefill"
    host = [it["wall_ns"] - it["fetch_ns"]
            for it in tickspans.iterations(run.spans)
            if it["decode"] and it["prefill"] == want_prefill
            and it["wall_ns"] is not None]
    if not host:
        return None
    ids = [ev[4]["id"] for ev in run.spans if ev[4] and "id" in ev[4]]
    log(f"iteration_host {which}: {len(host)} iterations, host ms median "
        f"{median(host) / 1e6:.3f} p90 {percentile(host, 90) / 1e6:.3f}; "
        f"{len(run.spans)} events in the window, highest span id "
        f"{max(ids)} (the recorder keeps 200000 events by default)")
    return median(host) / 1e6
