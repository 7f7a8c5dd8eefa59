"""Closed loop: callers that each wait for a reply. `clients` callers send
their next request when their last completes, so a slow system gets less
load and the measure is work completed per second.

Traffic file: `clients`, `ramp_s` before the window, `prompt_tokens`,
`new_tokens`, `sizes_seed`, `requests`, the length of the list the clients
draw from (more than a window can complete; the same sizes in the same
order for every seed, which draws the token ids and the weights), and
optionally `drain_s` (60), the longest wait after the window for the
requests then in flight.

End to end: `serve_tok_s`, the tokens SERVED inside the window over the
window. A request's prompt tokens are served between its admission and its
first token, and its new tokens between its first token and its end (the
request log's stamps); each part counts by the share of its span that lies
inside the window. So a request that straddles either end of the window
counts by what was done of it inside, and the callers stop sending at the
window's end but the requests in flight are served to their end, so that
their stamps exist. Counting whole requests by their completion, as this
did first, moves in steps of one request: 2.7 % of 38 in the first cell,
whichever side of the window's end the last one fell (PERF.md section 2).
"""

from __future__ import annotations

import queue
import threading
import time

from benchmark import serving
from benchmark.harness import Run, log


def run(run: Run) -> None:
    traffic, cfg = run.cell.traffic, run.cell.config
    reqs = serving.make_requests(traffic, int(traffic["requests"]), run.seed,
                                 cfg["vocab_size"])
    served = serving.Served(run)
    ramp = float(traffic["ramp_s"])
    t_base = time.monotonic() + 0.05
    t0, t1 = t_base + ramp, t_base + ramp + run.seconds
    stop = threading.Event()
    freed: "queue.Queue[int]" = queue.Queue()
    sent = []

    def dispatcher() -> None:
        """The clients: one thread sends for all of them, a new request
        for each completion it is told of."""
        serving.sleep_until(t_base)
        it = iter(reqs)
        for _ in range(int(traffic["clients"])):
            freed.put(1)
        while not stop.is_set():
            try:
                freed.get(timeout=0.05)
            except queue.Empty:
                continue
            req = next(it, None)
            if req is None:
                log("the request list ran out before the window ended")
                return
            served.submit(req)
            sent.append(req)
            if req.future is not None:
                req.future.add_done_callback(lambda _f: freed.put(1))

    thread = threading.Thread(target=dispatcher, name="bench-clients")
    thread.start()
    try:
        served.window(t0, t1)
        stop.set()
        thread.join()
        served.drain(sent, float(traffic.get("drain_s", 60.0)))
    finally:
        stop.set()
        thread.join()
        served.finish(sent, t0, t1)

    inside = [r for r in sent if r.record is not None
              and t0 <= r.record["done_ns"] / 1e9 < t1]
    errors = [r for r in sent if r.error not in (None, "cancelled")]
    run.attempted = len(inside) + len(errors)
    run.failed = len(errors)
    serving.request_rows(run, inside)
    tokens = sum(tokens_inside(r, t0, t1) for r in sent
                 if r.record is not None)
    run.e2e["serve_tok_s"] = tokens / run.seconds
    whole = sum(len(r.prompt) + r.new_tokens for r in inside)
    log(f"{tokens:.1f} tokens served inside the window, "
        f"{run.e2e['serve_tok_s']:.1f} tokens/s; {len(inside)} requests "
        f"of {whole} tokens completed inside it ({len(sent)} sent)")
    served.check(inside)


def tokens_inside(req: serving.Request, t0: float, t1: float) -> float:
    """The tokens of one completed request that were served in [t0, t1):
    the prompt spread evenly over admission -> first token, the new tokens
    over first token -> end."""
    rec = req.record
    admit, first, done = (rec[k] / 1e9 for k in
                          ("admit_ns", "first_token_ns", "done_ns"))

    def share(a: float, b: float) -> float:
        if b <= a:      # no span: all of it at the instant b
            return 1.0 if t0 <= b < t1 else 0.0
        return max(0.0, min(b, t1) - max(a, t0)) / (b - a)

    return (len(req.prompt) * share(admit, first)
            + req.new_tokens * share(first, done))
