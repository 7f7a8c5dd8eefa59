#!/usr/bin/env python
"""fftrace — trace/metrics tooling for the serving tick loop (obs/).

Subcommands:

  smoke [--out DIR] [--speculate]
      Build a tiny causal LM on CPU, serve a handful of requests through
      the paged scheduler (and the speculative server with --speculate,
      the default) with the span recorder + tick ledger enabled, then
      write into DIR (default ./fftrace_out):
        trace.json.gz    Chrome-trace / Perfetto trace_event JSON
        ledger.json      TickLedger with the priced base step stamped in
        calibration.json predicted-vs-measured report (fftrace calibrate)
        reqlog.jsonl     request-log flight-recorder export (obs.reqlog)
                         — the input to `fftrace replay` and
                         `servesearch search --replay`
      The last stdout line is a one-line JSON summary.

  replay REQLOG.jsonl [--out DIR] [--seed S] [--slots K] [--max-len L]
         [--page-size P] [--pace[=SPEEDUP]]
      Re-serve a recorded request log against the current (tiny smoke)
      server config: the log's RecordedProfile replays the recorded
      arrival order and prompt lengths (content re-drawn — logs never
      hold raw tokens) with each request's recorded decode budget, on a
      speculative server when the log recorded drafting. Reports
      recorded-vs-replayed TTFT/queue-time p50/p95 and tokens/s deltas.
      The default replay is a BURST (every request queued at once);
      --pace additionally replays the recorded interarrival deltas
      (sleeping each gap, divided by SPEEDUP) so the replayed
      percentiles are measured under the recorded arrival process and
      compare apples-to-apples — the report carries both modes' deltas.
      The last stdout line is the JSON report.

  calibrate LEDGER [--out FILE]
      Load a saved TickLedger and emit the calibration report: per
      tick-shape measured-vs-predicted ratios (the scale factors
      MeasuredCostModel.set_tick_calibration consumes) plus per-phase
      medians. Runs from the artifact alone — no model, no accelerator.
      Reports carry a schema version + created-at stamp (schema v2);
      consumers with a freshness window (the serving-strategy search,
      tools/servesearch.py) refuse reports older than 7 days.

  summarize TRACE
      Per-span-name counts, total and SELF time (a span's duration minus
      its children's, by the spans' id/parent links) and mean durations
      of a trace written by `smoke` (or
      TraceRecorder.export_chrome_trace), .gz or plain.

Open trace.json.gz directly in https://ui.perfetto.dev (it accepts
gzipped Chrome traces) — pid 1 is the tick loop, pid 2 the per-request
lifecycle tracks. See docs/observability.md.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_tiny_ff():
    """The bench/test smoke fixture: a tiny Llama compiled for serving."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=0))
    build_llama(ff, LlamaConfig.tiny(vocab=128), batch_size=1, seq_len=8,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def cmd_smoke(args) -> int:
    # CPU only: the smoke run must work headless in CI
    from flexflow_tpu.parallel.compat import ensure_cpu_devices

    ensure_cpu_devices(8)
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from flexflow_tpu import obs
    from flexflow_tpu.obs.calibrate import (
        calibration_report,
        stamp_ledger_meta,
    )

    out = args.out
    os.makedirs(out, exist_ok=True)
    ff = _build_tiny_ff()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (rs.randint(4, 13),)).astype(np.int32)
               for _ in range(args.requests)]

    rec = obs.enable()
    reqlog_records = []

    def serve(speculate=None):
        server = ff.serve_generation(slots=2, max_len=48, paged=True,
                                     page_size=8, speculate=speculate)
        try:
            futs = [server.submit(p, max_new_tokens=args.max_new)
                    for p in prompts]
            for f in futs:
                f.result(timeout=600)
            return server.metrics()
        finally:
            # flight-recorder export rides the same smoke run: the
            # plain and speculative passes append to one reqlog.jsonl
            reqlog_records.extend(server.request_log.records())
            server.stop()

    try:
        serve()  # plain paged: decode + prefill tick shapes
        if args.speculate:
            from flexflow_tpu.spec import SpecConfig

            serve(SpecConfig(width=2, depth=3))  # verify tick shapes
    finally:
        obs.disable()

    stamp_ledger_meta(rec.ledger, ff, fixture="fftrace smoke")
    trace_path = rec.export_chrome_trace(os.path.join(out, "trace.json.gz"))
    ledger_path = rec.ledger.save(os.path.join(out, "ledger.json"))
    report = calibration_report(rec.ledger)
    calib_path = os.path.join(out, "calibration.json")
    with open(calib_path, "w") as f:
        json.dump(report, f, indent=1)
    from flexflow_tpu.obs import reqlog as reqlog_mod

    reqlog_path = os.path.join(out, "reqlog.jsonl")
    n_logged = reqlog_mod.dump_jsonl(reqlog_path, reqlog_records)

    print(json.dumps({
        "trace": trace_path,
        "ledger": ledger_path,
        "calibration": calib_path,
        "reqlog": reqlog_path,
        "reqlog_records": n_logged,
        "schema_version": report["version"],
        "created_at": report["created_at"],
        "events": len(rec.events),
        "requests": len(rec.requests),
        "shapes": sorted(report["tick_scales"]),
        "phases": {k: round(v, 3) for k, v in report["phases"].items()},
    }))
    return 0


def cmd_replay(args) -> int:
    from flexflow_tpu.parallel.compat import ensure_cpu_devices

    ensure_cpu_devices(8)
    import jax

    jax.config.update("jax_platforms", "cpu")

    import time

    import numpy as np

    from flexflow_tpu.obs.slo import percentile
    from flexflow_tpu.search.traffic import RecordedProfile

    profile = RecordedProfile.from_reqlog(args.log)

    def _stats(records):
        ttfts = [(r["first_token_ns"] - r["submit_ns"]) / 1e9
                 for r in records]
        queues = [max(0.0, (r["admit_ns"] - r["submit_ns"]) / 1e9)
                  for r in records]
        makespan = (max(r["done_ns"] for r in records)
                    - min(r["submit_ns"] for r in records)) / 1e9
        toks = sum(int(r.get("decode_tokens", 0)) for r in records)
        return {
            "requests": len(records),
            "ttft_p50_s": percentile(ttfts, 0.5),
            "ttft_p95_s": percentile(ttfts, 0.95),
            "queue_p50_s": percentile(queues, 0.5),
            "queue_p95_s": percentile(queues, 0.95),
            "decode_tokens": toks,
            "tokens_per_s": toks / makespan if makespan > 0 else 0.0,
        }

    _DELTA_KEYS = ("ttft_p50_s", "ttft_p95_s", "queue_p50_s",
                   "queue_p95_s", "tokens_per_s")
    recorded = _stats(profile.records)
    ff = _build_tiny_ff()
    speculate = None
    if profile.measured_acceptance() is not None:
        # the log drafted, so the replay drafts: same server family
        from flexflow_tpu.spec import SpecConfig

        speculate = SpecConfig(width=2, depth=3)

    def _serve(pace):
        """One replay pass. pace=None submits in recorded ORDER only
        (burst — every request queued at once, the worst case); a
        float sleeps the recorded interarrival deltas compressed by
        that speedup factor, so queue-time and TTFT percentiles are
        measured under the recorded arrival PROCESS and compare
        directly to the log's own."""
        rs = np.random.RandomState(args.seed)
        sampled = profile.sample(rs, vocab=128)
        server = ff.serve_generation(
            slots=args.slots, max_len=args.max_len, paged=True,
            page_size=args.page_size, speculate=speculate)
        try:
            budgets = profile.new_tokens_per_request
            submit_ns = [r["submit_ns"] for r in profile.records]
            futs = []
            for i, p in enumerate(sampled.prompts):
                if pace and i > 0:
                    delta = (submit_ns[i % len(submit_ns)]
                             - submit_ns[(i - 1) % len(submit_ns)])
                    if delta > 0:
                        time.sleep(delta / 1e9 / pace)
                futs.append(server.submit(
                    p, max_new_tokens=budgets[i % len(budgets)]))
            for f in futs:
                f.result(timeout=600)
            return _stats(server.request_log.records())
        finally:
            server.stop()

    replayed = _serve(None)
    doc = {
        "log": args.log,
        "profile": profile.name,
        "speculate": speculate is not None,
        "recorded": recorded,
        "replayed": replayed,
        "delta": {k: replayed[k] - recorded[k] for k in _DELTA_KEYS},
    }
    if args.pace is not None:
        # both modes ride one report: the burst numbers above show the
        # config's queueing worst case, the paced numbers are the
        # apples-to-apples comparison against the recorded percentiles
        paced = _serve(args.pace)
        doc["paced"] = {
            "speedup": args.pace,
            "replayed": paced,
            "delta": {k: paced[k] - recorded[k] for k in _DELTA_KEYS},
        }
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out, "replay_report.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        doc["report"] = path
    print(json.dumps(doc))
    return 0


def cmd_calibrate(args) -> int:
    from flexflow_tpu.obs.ledger import TickLedger
    from flexflow_tpu.obs.calibrate import calibration_report

    led = TickLedger.load(args.ledger)
    try:
        report = calibration_report(led)
    except ValueError as e:
        print(f"fftrace calibrate: {e}", file=sys.stderr)
        return 2
    doc = json.dumps(report, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")
        print(args.out)
    else:
        print(doc)
    return 0


def cmd_summarize(args) -> int:
    opener = gzip.open if args.trace.endswith(".gz") else open
    with opener(args.trace, "rt") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    spans = [ev for ev in events if ev.get("ph") == "X"]
    # time under each span's children, by the parent links spans carry
    under = {}
    for ev in spans:
        parent = (ev.get("args") or {}).get("parent")
        if parent is not None:
            under[parent] = under.get(parent, 0.0) + float(ev.get("dur", 0.0))
    by_name = {}
    for ev in spans:
        name = ev["name"].split(":", 1)[0]  # collapse per-request labels
        dur = float(ev.get("dur", 0.0))
        n, total, self_ = by_name.get(name, (0, 0.0, 0.0))
        by_name[name] = (n + 1, total + dur, self_ + dur - under.get(
            (ev.get("args") or {}).get("id"), 0.0))
    width = max((len(n) for n in by_name), default=4)
    print(f"{'span':<{width}}  {'count':>6}  {'total_ms':>10}  "
          f"{'self_ms':>10}  {'mean_us':>9}")
    for name, (n, total, self_) in sorted(by_name.items(),
                                          key=lambda kv: -kv[1][1]):
        print(f"{name:<{width}}  {n:>6}  {total / 1e3:>10.2f}  "
              f"{self_ / 1e3:>10.2f}  {total / n:>9.1f}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="fftrace", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    sm = sub.add_parser("smoke", help="traced tiny-model serving run")
    sm.add_argument("--out", default="fftrace_out")
    sm.add_argument("--requests", type=int, default=4)
    sm.add_argument("--max-new", type=int, default=8)
    sm.add_argument("--no-speculate", dest="speculate", action="store_false")
    sm.set_defaults(func=cmd_smoke, speculate=True)

    rp = sub.add_parser("replay", help="re-serve a recorded request log")
    rp.add_argument("log", help="reqlog JSONL export (fftrace smoke / "
                                "server.request_log.export_jsonl)")
    rp.add_argument("--out", default=None,
                    help="also write replay_report.json into this dir")
    rp.add_argument("--seed", type=int, default=0)
    rp.add_argument("--slots", type=int, default=2)
    rp.add_argument("--max-len", type=int, default=48)
    rp.add_argument("--page-size", type=int, default=8)
    rp.add_argument("--pace", nargs="?", const=1.0, type=float,
                    default=None, metavar="SPEEDUP",
                    help="ALSO run a paced replay sleeping the recorded "
                         "interarrival deltas (divided by SPEEDUP, "
                         "default 1.0 = real time) — the report then "
                         "carries both modes' recorded-vs-replayed "
                         "deltas")
    rp.set_defaults(func=cmd_replay)

    ca = sub.add_parser("calibrate", help="predicted-vs-measured report")
    ca.add_argument("ledger")
    ca.add_argument("--out", default=None)
    ca.set_defaults(func=cmd_calibrate)

    su = sub.add_parser("summarize", help="per-span totals of a trace")
    su.add_argument("trace")
    su.set_defaults(func=cmd_summarize)

    args = ap.parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
