"""Find an open-loop cell's knee, once, on the chip.

    python3 benchmark/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 20

One process and one set-up: the server of the cell is built and warmed, then
the cell's own traffic is offered at each rate in turn (and, with `--seeds`,
once per seed at each rate) for `--ramp` + `--seconds`, with a full drain in
between. For each it prints how many requests were due in the window, how
many of them were unfinished at its middle and at its end (a backlog that
grows between the two is past the knee), TTFT and TPOT, and the tokens per
second completed inside the window. The knee is read
off by the builder and four fifths of it written into the traffic file as a
number: the benchmark itself never searches for a rate.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="1",
                    help="each rate is offered once per seed")
    ap.add_argument("--ramp", type=float, default=5.0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import device, harness, serving, spec
    from benchmark.stats import median, percentile
    from benchmark.traffic_kinds import open_poisson

    cell = spec.load(ROOT)["cells"][args.workload]
    try:
        dev = device.demand_tpu(cell.chips)
    except device.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    from flexflow_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    run = harness.Run(cell=cell, seed=0, seconds=args.seconds,
                      trace=False, root=ROOT,
                      t_process_start=T_PROCESS_START, device=dev,
                      compile_clock=harness.CompileClock())
    served = serving.Served(run)
    harness.log(f"set-up {time.monotonic() - T_PROCESS_START:.1f} s")
    seeds = [int(x) for x in args.seeds.split(",")]
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            traffic = dict(cell.traffic, rate_rps=rate, ramp_s=args.ramp)
            reqs = open_poisson.schedule(traffic, seed, args.seconds,
                                         cell.config["vocab_size"])
            judged, t0, t1 = open_poisson.offer(
                served, reqs, args.ramp, args.seconds, drain=120.0)
            while any(r.tokens is None and r.error is None for r in reqs):
                time.sleep(0.05)
            served.join_records(reqs)
            late = sum(1 for r in judged if r.record is None
                       or r.record["done_ns"] / 1e9 > t1)
            mid = (t0 + t1) / 2
            half = sum(1 for r in judged if r.due_t < mid and (
                r.record is None or r.record["done_ns"] / 1e9 > mid))
            run.requests = []
            serving.request_rows(run, judged)
            rows = [r for r in run.requests if r["done"]]
            ttft = [r["ttft_ms"] for r in rows]
            tpot = [r["tpot_ms"] for r in rows]
            tokens = sum(r["prompt_tokens"] + r["new_tokens"] for r in rows
                         if r["done_t"] < t1)
            harness.log(
                f"rate {rate:g}/s seed {seed}: judged {len(judged)} done "
                f"{len(rows)} unfinished at half {half} at end {late}; "
                f"ttft p50 {median(ttft):.0f} p90 {percentile(ttft, 90):.0f}"
                f" ms; tpot p50 {median(tpot):.2f} p90 "
                f"{percentile(tpot, 90):.2f} ms; "
                f"{tokens / args.seconds:.0f} tokens/s completed in the "
                f"window; preemptions "
                f"{served.server.metrics()['preemptions']}")
    harness.log(f"peak memory {device.memory_peak_bytes(cell.chips)} bytes")
    served.server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
