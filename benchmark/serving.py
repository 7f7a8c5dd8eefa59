"""What the serving traffic kinds share: the server as the configuration
states it, request sizes drawn from a seed, the window's counters, the
per-request rows and the comparison with the plain reference.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.harness import DeviceTrace, Run, log

# spans of the program's tick loop (flexflow_tpu/obs, paged/scheduler.py)
# that idle gaps of the device are attributed to
TICK_SPANS = ("tick_prep", "admit_pending", "idle_wait", "defrag",
              "prefill_tick", "decode_tick")
TRACE_START_S = 2.0     # into the window
TRACE_SECONDS = 5.0


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray
    new_tokens: int
    due_s: Optional[float] = None      # offset from the start of the ramp
    # stamped while it is served (time.monotonic seconds, the clock the
    # program's request log uses)
    due_t: Optional[float] = None
    send_t: Optional[float] = None
    future: object = None
    rid: Optional[int] = None          # the program's request-log id
    record: Optional[Dict] = None      # the program's request-log record
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None


def draw_lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n whole lengths from {"dist": "lognormal", "median", "sigma", "min",
    "max"} or {"dist": "uniform", "min", "max"} (both ends included)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        raw = rng.integers(lo, hi + 1, n).astype(np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), lo, hi).astype(np.int64)


def make_requests(traffic: Dict, n: int, seed: int, vocab: int
                  ) -> List[Request]:
    """n requests. Their SIZES, in their order, are the traffic file's own
    (`sizes_seed`): a fixed trace. `seed` draws the token ids (and, in the
    caller, the weights), so every seed gives the system the same work in
    the same order on other values. Measured on the chip (PERF.md section
    2): with the order drawn from the seed, a tail over 80 requests swung
    by 10-14 % from seed to seed, and once 3.5-fold, with which long prompt
    met which burst. No two prompts share a prefix but by chance."""
    base = np.random.default_rng(int(traffic["sizes_seed"]))
    prompts = draw_lengths(traffic["prompt_tokens"], n, base)
    news = draw_lengths(traffic["new_tokens"], n, base)
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32), int(news[i]))
            for i in range(n)]


class Served:
    """The model and server of a serving cell, warmed; one per run."""

    def __init__(self, run: Run):
        cfg = run.cell.config
        self.run = run
        self.recorder = None
        if run.trace:
            from flexflow_tpu import obs

            self.recorder = obs.enable()
        t0 = time.monotonic()
        self.ff = run.family().build_server_model(cfg, run.program_seed())
        log(f"model built in {time.monotonic() - t0:.1f} s")
        t0 = time.monotonic()
        self.server = self.ff.serve_generation(**cfg["server"])
        catalog = self.server.warm_launch_shapes()
        self.launch_shapes = int(catalog["total_compilations"])
        log(f"warmed {self.launch_shapes} launch shapes in "
            f"{time.monotonic() - t0:.1f} s")
        self.at_start = None
        self.at_end = None

    # -- sending ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Send one request now. Its completion, in the server's own loop
        thread, notes the program's request-log id."""
        req.send_t = time.monotonic()
        try:
            fut = self.server.submit(req.prompt, req.new_tokens)
        except (ValueError, RuntimeError) as e:
            req.error = f"{type(e).__name__}: {e}"
            return
        req.future = fut
        fut.add_done_callback(lambda f, r=req: self._done(r, f))

    def _done(self, req: Request, fut) -> None:
        if fut.cancelled():
            req.error = req.error or "cancelled"
        elif fut.exception() is not None:
            req.error = repr(fut.exception())
        else:
            req.rid = self.server.requests_served
            req.tokens = np.asarray(fut.result())

    def join_records(self, requests: List[Request]) -> None:
        """Give each completed request the program's request-log record."""
        records = {r["rid"]: r for r in self.server.request_log.records()}
        for req in requests:
            if req.rid is not None:
                req.record = records.get(req.rid)

    # -- the window ------------------------------------------------------

    def window(self, t0: float, t1: float) -> None:
        """Called from the thread that does nothing else: sleep to the
        window's start, take the counters, trace a few seconds of it if
        this is the traced run, sleep to its end, take the counters."""
        run = self.run
        self.t0, self.t1 = t0, t1
        sleep_until(t0)
        self.at_start = self.server.metrics()
        run.setup_s = t0 - run.t_process_start
        run.counters["setup_compile_s"] = run.compile_clock.seconds
        events_at_start = run.compile_clock.events
        if run.trace:
            trace = DeviceTrace(run, TICK_SPANS)
            sleep_until(min(t0 + TRACE_START_S, t0 + 0.2 * (t1 - t0)))
            trace.start()
            sleep_until(min(time.monotonic() + TRACE_SECONDS,
                             t0 + 0.8 * (t1 - t0)))
            trace.stop()
            self.trace = trace
        sleep_until(t1)
        self.at_end = self.server.metrics()
        run.counters["window_compile_events"] = (
            run.compile_clock.events - events_at_start)

    def drain(self, requests: List[Request], longest_s: float) -> None:
        """After the window, with nothing more sent: wait for the requests
        in flight to end, so that each has its record. One that does not
        end in `longest_s` counts as failed."""
        t = time.monotonic()
        left = concurrent.futures.wait(
            [r.future for r in requests if r.future is not None],
            timeout=longest_s).not_done
        for req in requests:
            if req.future in left:
                req.error = f"not served {longest_s:.0f} s after the window"
        log(f"requests in flight drained in {time.monotonic() - t:.1f} s, "
            f"{len(left)} left")

    def finish(self, requests: List[Request], t0: float, t1: float) -> None:
        """Stop the server, join each request with the program's record of
        it, fill the window's counters and the spans inside the window."""
        run = self.run
        final = self.server.metrics()
        self.join_records(requests)
        self.server.stop()
        self.server = None      # its pools are free before the reference runs
        a, b = self.at_start, self.at_end
        c = run.counters
        for key in ("requests_served", "preemptions", "launch_rows",
                    "padded_rows", "prefill_ticks", "decode_steps"):
            c[key] = b[key] - a[key]
        c["prefix_hit_tokens"] = (b["prefix_cache"]["hit_tokens"]
                                  - a["prefix_cache"]["hit_tokens"])
        c["launch_shapes"] = self.launch_shapes
        c["steady_state_recompiles"] = final["compile"][
            "steady_state_recompiles"]
        run.extras["kernel_variant"] = final["kernel_variant"]
        run.extras["kv_cache_dtype"] = final["kv_cache_dtype"]
        if self.recorder is not None:
            from flexflow_tpu import obs

            obs.disable()
            lo, hi = t0 * 1e9, t1 * 1e9
            run.spans = [ev for ev in self.recorder.events
                         if lo <= ev[1] and ev[1] + ev[2] <= hi]
            self.trace.reduce()
        log(f"window counters: {c}")

    # -- correctness -----------------------------------------------------

    def check(self, done: List[Request]) -> None:
        """Outside the window: a seeded sample of completed requests
        against the plain reference, fed the program's own weights. Over
        prompt + served tokens, each served token must be the reference's
        argmax or within `tie_tol_sigma` standard deviations of the row's
        logits below it (the configuration file says why)."""
        import jax
        import jax.numpy as jnp

        run = self.run
        cfg, chk = run.cell.config, run.cell.config["check"]
        why = run.why_not
        if run.extras["kernel_variant"] != chk["kernel_variant"]:
            why.append(f"kernel_variant={run.extras['kernel_variant']}")
        if run.extras["kv_cache_dtype"] != chk["kv_cache_dtype"]:
            why.append(f"kv_cache_dtype={run.extras['kv_cache_dtype']}")
        if run.counters["steady_state_recompiles"]:
            why.append("the program compiled inside the window")
        if not done:
            why.append("no request completed")
            return
        fam = run.family()
        logits = fam.reference_logits(cfg)

        def gaps(w, ids):
            lg = logits(w, ids)
            nxt = jnp.roll(ids, -1)
            taken = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
            return (lg.max(-1) - taken) / lg.std(-1), jnp.isfinite(lg).all()

        gaps = jax.jit(gaps)
        weights = fam.reference_weights(self.ff._params[0], cfg)
        rng = np.random.default_rng(run.seed)
        pick = rng.choice(len(done), min(int(chk["sample"]), len(done)),
                          replace=False)
        traffic = run.cell.traffic
        longest = (int(traffic["prompt_tokens"]["max"])
                   + int(traffic["new_tokens"]["max"]))
        width = -(-longest // 512) * 512   # one shape, so one compile
        tol = float(chk["tie_tol_sigma"])
        exact = ties = misses = 0
        worst = 0.0
        for i in pick:
            req = done[int(i)]
            n_p, n_t = len(req.prompt), len(req.tokens)
            ids = np.zeros((width,), np.int32)
            ids[:n_p] = req.prompt
            ids[n_p:n_p + n_t] = req.tokens
            if (n_t != req.new_tokens or req.tokens.min() < 0
                    or req.tokens.max() >= cfg["vocab_size"]):
                why.append(f"request {req.index}: bad tokens")
                continue
            g, finite = gaps(weights, jnp.asarray(ids))
            if not bool(finite):
                why.append("reference logits are not finite")
                continue
            g = np.asarray(g)[n_p - 1:n_p - 1 + n_t]
            exact += int((g == 0.0).sum())
            ties += int(((g > 0.0) & (g <= tol)).sum())
            misses += int((g > tol).sum())
            worst = max(worst, float(g.max()))
        log(f"reference check: {len(pick)} requests, {exact} tokens are the "
            f"reference argmax, {ties} within {tol} sigma of it, {misses} "
            f"beyond; largest gap {worst:.4f} sigma")
        if misses:
            why.append(f"{misses} served tokens are more than {tol} sigma "
                       "below the reference argmax")
        run.correct = not why


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(left)


def request_rows(run: Run, judged: List[Request]) -> None:
    """One row per judged request for the readers, times in ms."""
    for req in judged:
        rec = req.record
        row = {"prompt_tokens": len(req.prompt),
               "new_tokens": req.new_tokens,
               "done": rec is not None}
        if req.due_t is not None and req.send_t is not None:
            row["gen_late_ms"] = (req.send_t - req.due_t) * 1e3
        if rec is not None:
            origin = req.due_t if req.due_t is not None else req.send_t
            first, done = rec["first_token_ns"] / 1e9, rec["done_ns"] / 1e9
            row["ttft_ms"] = (first - origin) * 1e3
            row["queue_wait_ms"] = (rec["admit_ns"] / 1e9 - origin) * 1e3
            row["tpot_ms"] = ((done - first) * 1e3
                              / max(req.new_tokens - 1, 1))
            row["done_t"] = done
            row["preemptions"] = rec["preemptions"]
        run.requests.append(row)
