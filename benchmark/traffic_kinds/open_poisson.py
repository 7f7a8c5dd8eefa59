"""Open loop: independent users. Requests are DUE on a schedule, whatever
the server does, and each is timed from when it was due.

Traffic file: `rate_rps` (a number, fixed in the file, never searched for
here), `ramp_s` of the same traffic before the window, `drain_s` after it,
`prompt_tokens` and `new_tokens` (see `serving.draw_lengths`), `sizes_seed`.
The schedule spans ramp + window and holds round(rate x span) arrivals whose
gaps are exponential (one sample of a Poisson process, drawn from the file's
`sizes_seed`) and scaled to fill the span: a fixed trace of arrivals and sizes,
the same for every seed. The seed draws the token ids and the weights.

End to end: `ttft_p90` and `tpot_p90` over the requests DUE inside the
window. One that has not completed `drain_s` after the window counts as
failed, and misses any limit.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmark import serving
from benchmark.harness import Run, log
from benchmark.stats import median, percentile


def schedule(traffic: Dict, seed: int, seconds: float, vocab: int
             ) -> List[serving.Request]:
    """The requests with their due offsets from the start of the ramp; a
    pure function of its arguments."""
    span = float(traffic["ramp_s"]) + float(seconds)
    n = max(1, int(round(float(traffic["rate_rps"]) * span)))
    reqs = serving.make_requests(traffic, n, seed, vocab)
    base = np.random.default_rng(int(traffic["sizes_seed"]) + 1)
    gaps = base.exponential(1.0, n)
    due = (np.cumsum(gaps) - gaps[0] / 2) / gaps.sum() * span
    for req, d in zip(reqs, due):
        req.due_s = float(d)
    return reqs


def start_sender(served, reqs: List[serving.Request], t_base: float):
    """One thread sends every request when it is due, counted from
    `t_base`; returns (stop event, thread)."""
    stop = threading.Event()

    def sender() -> None:
        for req in reqs:
            req.due_t = t_base + req.due_s
            while not stop.is_set():
                left = req.due_t - time.monotonic()
                if left <= 0:
                    break
                time.sleep(min(left, 0.05))
            if stop.is_set():
                return
            served.submit(req)

    thread = threading.Thread(target=sender, name="bench-sender")
    thread.start()
    return stop, thread


def offer(served, reqs: List[serving.Request], ramp: float, seconds: float,
          drain: float, during=None):
    """Offer the schedule: `ramp` seconds of it, then the window, then up
    to `drain` seconds for the requests due inside the window to finish.
    `during(t0, t1)` runs in this thread over the window. Returns the
    judged requests and the window's ends."""
    t_base = time.monotonic() + 0.05
    t0, t1 = t_base + ramp, t_base + ramp + seconds
    stop, thread = start_sender(served, reqs, t_base)
    try:
        if during is not None:
            during(t0, t1)
        serving.sleep_until(t1)
        judged = [r for r in reqs if t0 <= t_base + r.due_s < t1]
        deadline = t1 + drain
        for req in judged:
            while req.tokens is None and req.error is None \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
    finally:
        stop.set()
        thread.join()
    return judged, t0, t1


def run(run: Run) -> None:
    traffic, cfg = run.cell.traffic, run.cell.config
    reqs = schedule(traffic, run.seed, run.seconds, cfg["vocab_size"])
    served = serving.Served(run)
    try:
        judged, t0, t1 = offer(served, reqs, float(traffic["ramp_s"]),
                               run.seconds, float(traffic["drain_s"]),
                               during=served.window)
    finally:
        if served.at_end is not None:
            served.finish(reqs, served.t0, served.t1)
        else:
            served.server.stop()

    done = [r for r in judged if r.record is not None]
    run.attempted = len(judged)
    run.failed = len(judged) - len(done)
    serving.request_rows(run, judged)
    rows = [r for r in run.requests if r["done"]]
    if rows:
        ttft = [r["ttft_ms"] for r in rows]
        tpot = [r["tpot_ms"] for r in rows]
        late = [r["gen_late_ms"] for r in run.requests]
        run.e2e["ttft_p90"] = percentile(ttft, 90)
        run.e2e["tpot_p90"] = percentile(tpot, 90)
        log(f"{len(rows)} of {len(judged)} judged requests completed; "
            f"ttft median {median(ttft):.1f} ms p90 "
            f"{run.e2e['ttft_p90']:.1f} ms; tpot median "
            f"{median(tpot):.2f} ms p90 {run.e2e['tpot_p90']:.2f} ms; "
            f"generator late median {median(late):.3f} ms max "
            f"{max(late):.3f} ms; backlog at window end "
            f"{sum(1 for r in judged if r.record is None or r.record['done_ns'] / 1e9 > t1)}")
        tokens = sum(r["prompt_tokens"] + r["new_tokens"] for r in rows
                     if r["done_t"] < t1)
        log(f"completed inside the window: {tokens / run.seconds:.1f} "
            "tokens/s (offered load, not a metric of this cell)")
    served.check(done)
