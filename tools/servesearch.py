#!/usr/bin/env python
"""servesearch — search / explain / apply serving strategies
(flexflow_tpu.search.servesearch, docs/search.md "Serving strategy
search").

Subcommands:

  search [--profile NAME | --replay REQLOG.jsonl] [--budget N]
         [--seed S] [--slots K]
         [--max-len L] [--calibration REPORT.json] [--hbm-budget BYTES]
         [--acceptance-rate A] [--mesh-layouts SPEC] [--inner-budget M]
         [--out FILE]
      Build the tiny smoke model on CPU, run the serving-strategy
      search against the named traffic profile
      (flexflow_tpu.search.traffic: smoke, shared-system-prompt,
      mixed-length, long-context-summarization, agentic-multiturn) —
      or, with --replay, against a RECORDED request log
      (obs.reqlog JSONL from `server.request_log.export_jsonl` or
      `fftrace smoke`): prompt moments, prefix share, arrival process
      and spec acceptance are then MEASURED from the log
      (search/traffic.py RecordedProfile) — and write the full result
      JSON — winning
      ServeStrategy, simulated SLO metrics for it and the hand default,
      per-layout step prices, calibration provenance. A fresh `fftrace
      calibrate` report sharpens the tick prices; stale reports are
      refused with a warning. --mesh-layouts takes
      "data=8;data=2,model=4" — candidate serving meshes each
      shard-searched by the existing MCMC driver for --inner-budget
      iterations. With --sim (and --replay) every candidate is scored
      by the EVENT-DRIVEN tick simulator (search/ticksim.py) replaying
      the log's recorded arrival sequence instead of the closed-form
      pricer, so bursts and queue depth shape the pick. The last
      stdout line is a one-line JSON summary.

  simulate REQLOG.jsonl [--strategy STRATEGY.json] [--slots K]
           [--max-len L] [--seed S] [--out TIMELINE.json]
      Replay a recorded request log through the discrete-event tick
      simulator under one strategy: per-request TTFT/queue/decode
      timelines (--out writes the JSON), burst-aware p50/p95, and the
      closed-form TTFT p95 alongside for contrast.

  explain RESULT.json [--calibration REPORT.json]
      Human-readable breakdown of a search result: the winning knobs,
      each objective term (TTFT / throughput / HBM penalty) for the
      searched and default strategies, the priced tick metrics behind
      them, and a compile_cost line per strategy — the enumerated
      launch-shape catalog size (analysis.shapecheck) times the
      measured per-compile median from the calibration report's
      compile block (or a rough estimate without one), so a strategy
      with 40 launch shapes visibly pays warmup a 6-shape strategy
      doesn't.

  apply RESULT.json [--out FILE] [--serve-smoke]
      Emit the winning strategy as the JSON `serve_generation(
      serve_strategy=...)` loads (also accepted by FFModel
      .serve_generation). --serve-smoke builds the tiny model, serves a
      few prompts under the strategy and asserts token identity with
      dense generate() — proof the searched config is servable.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_tiny_ff():
    from flexflow_tpu.parallel.compat import ensure_cpu_devices

    ensure_cpu_devices(8)
    import jax

    jax.config.update("jax_platforms", "cpu")

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=0))
    build_llama(ff, LlamaConfig.tiny(vocab=128), batch_size=1, seq_len=8,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _parse_layouts(spec):
    """'data=8;data=2,model=4' -> [{'data': 8}, {'data': 2, 'model': 4}]"""
    if not spec:
        return None
    layouts = []
    for part in spec.split(";"):
        axes = {}
        for kv in part.split(","):
            k, v = kv.split("=")
            axes[k.strip()] = int(v)
        layouts.append(axes)
    return layouts


def cmd_search(args) -> int:
    from flexflow_tpu.search.servesearch import (
        ServeObjective,
        search_serve_strategy,
    )

    traffic = args.profile
    if args.replay:
        # score candidates against RECORDED traffic: the reqlog export
        # becomes the profile, and its measured stats (prompt moments,
        # arrival process, realized spec acceptance) feed the pricer
        from flexflow_tpu.search.traffic import RecordedProfile

        traffic = RecordedProfile.from_reqlog(args.replay)
    ff = _build_tiny_ff()
    objective = None
    if args.hbm_budget is not None:
        objective = ServeObjective(hbm_budget_bytes=float(args.hbm_budget))
    res = search_serve_strategy(
        ff, traffic=traffic, budget=args.budget, seed=args.seed,
        slots=args.slots, max_len=args.max_len, objective=objective,
        calibration=args.calibration, acceptance_rate=args.acceptance_rate,
        layouts=_parse_layouts(args.mesh_layouts),
        inner_budget=args.inner_budget, sim=args.sim)
    doc = res.to_json()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    print(json.dumps({
        "profile": res.traffic,
        "backend": res.backend,
        "best": res.best.describe(),
        "best_objective": res.best_objective,
        "default_objective": res.default_objective,
        "improvement": round(res.improvement, 4),
        "trials": res.trials,
        "calibration": res.calibration,
        "acceptance": res.acceptance,
        "arrival": res.arrival,
        "out": args.out,
    }))
    return 0


def cmd_simulate(args) -> int:
    from flexflow_tpu.search.servesearch import ServeStrategy, build_pricer
    from flexflow_tpu.search.ticksim import TickSimulator
    from flexflow_tpu.search.traffic import RecordedProfile

    import dataclasses

    profile = RecordedProfile.from_reqlog(args.reqlog)
    strategy = ServeStrategy()
    # default knobs clamp to the serving window, same as the search
    strategy = dataclasses.replace(
        strategy, page_size=min(strategy.page_size, args.max_len),
        prefill_chunk=min(strategy.prefill_chunk, args.max_len))
    if args.strategy:
        with open(args.strategy) as f:
            doc = json.load(f)
        # accept a bare strategy JSON (servesearch apply --out) or a
        # full search result (its `best` is the strategy)
        if isinstance(doc.get("best"), dict):
            doc = doc["best"]
        strategy = ServeStrategy.from_json(doc)
    ff = _build_tiny_ff()
    pricer = build_pricer(ff, traffic=profile, slots=args.slots,
                          max_len=args.max_len,
                          calibration=args.calibration)
    sim = TickSimulator(pricer).simulate(strategy, profile,
                                         seed=args.seed)
    closed = pricer.metrics(strategy)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(sim.timeline_json(), f, indent=1, sort_keys=True)
    print(json.dumps({
        "reqlog": args.reqlog,
        "strategy": strategy.describe(),
        "requests": len(sim.records),
        "ticks": sim.ticks,
        "preemptions": sim.preemptions,
        "makespan_s": round(sim.makespan_s, 6),
        "sim_ttft_p95_s": round(sim.metrics["ttft_p95_s"], 6),
        "sim_queue_p95_s": round(sim.metrics["queue_p95_s"], 6),
        "sim_tokens_per_s": round(sim.metrics["tokens_per_s"], 2),
        "closed_form_ttft_p95_s": round(closed["ttft_p95_s"], 6),
        "out": args.out,
    }))
    return 0


def _fmt_metrics(m) -> str:
    return (f"    TTFT p95         {m['ttft_p95_s'] * 1e3:10.4f} ms\n"
            f"    tokens/sec       {m['tokens_per_s']:10.1f}\n"
            f"    HBM resident     {m['hbm_bytes'] / 1e6:10.2f} MB "
            f"({m['pool_pages']:.0f} pool pages, "
            f"occupancy {m['pool_occupancy']:.2f})\n"
            f"    padding waste    {m['padding_waste_ratio']:10.3f}\n"
            f"    accepted/step    {m['expected_accepted_per_step']:10.2f}")


# per-compile wall time when no calibration artifact supplies the
# measured median (rough CPU-smoke figure; real runs should pass
# --calibration so the warmup price is measured, not guessed)
UNCALIBRATED_COMPILE_S = 0.5


def _compile_seconds_p50(calibration_path):
    """(seconds_per_compile, 'measured'|'uncalibrated estimate') from an
    fftrace calibrate report's compile block, when one is supplied and
    carries one."""
    if calibration_path:
        try:
            with open(calibration_path) as f:
                comp = json.load(f).get("compile") or {}
            if comp.get("seconds_p50"):
                return float(comp["seconds_p50"]), "measured"
        except (OSError, ValueError):
            pass
    return UNCALIBRATED_COMPILE_S, "uncalibrated estimate"


def cmd_explain(args) -> int:
    from flexflow_tpu.analysis.shapecheck import catalog_for_strategy
    from flexflow_tpu.search.servesearch import ServeSearchResult

    with open(args.result) as f:
        res = ServeSearchResult.from_json(json.load(f))
    per_compile_s, compile_src = _compile_seconds_p50(
        getattr(args, "calibration", None))
    print(f"profile: {res.traffic}  (slots={res.slots}, "
          f"max_len={res.max_len}, budget={res.budget}, seed={res.seed}, "
          f"{res.trials} strategies priced)")
    cal = res.calibration
    if cal and cal.get("used"):
        print(f"calibration: fftrace report v{cal.get('version')} from "
              f"{cal.get('created_at')} ({cal.get('shapes')} tick shapes)")
    elif cal:
        print(f"calibration: NOT used ({cal.get('reason')})")
    else:
        print("calibration: none supplied (analytic tick prices)")
    for lay in res.layouts:
        print(f"layout {lay['mesh']}: step {lay['step_s'] * 1e3:.4f} ms "
              f"({lay['pricing_mode']}), kv {lay['kv_token_bytes']} B/token")
    for label, strat, obj, m in (
            ("searched", res.best, res.best_objective, res.best_metrics),
            ("default ", res.default, res.default_objective,
             res.default_metrics)):
        terms = res.objective.breakdown(m)
        print(f"\n{label}: {strat.describe()}")
        print(f"  objective {obj:.6f}  =  ttft {terms['ttft_term']:.6f} "
              f"+ throughput {terms['throughput_term']:.6f} "
              f"+ hbm penalty {terms['hbm_penalty']:.6f}")
        print(_fmt_metrics(m))
        # warmup price of this strategy's launch-shape space
        # (analysis.shapecheck): every enumerated shape is one compile
        # the server pays before its first steady-state token
        cat = catalog_for_strategy(strat, slots=res.slots,
                                   max_len=res.max_len)
        n_shapes = cat["total_compilations"]
        print(f"    compile_cost     {n_shapes:4d} launch shapes x "
              f"{per_compile_s:.3f} s/compile = "
              f"{n_shapes * per_compile_s:8.2f} s warmup "
              f"({compile_src})")
    print(f"\nimprovement over default: {res.improvement * 100:.1f}%")
    return 0


def cmd_apply(args) -> int:
    from flexflow_tpu.search.servesearch import ServeSearchResult

    with open(args.result) as f:
        res = ServeSearchResult.from_json(json.load(f))
    strategy = res.best.to_json()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(strategy, f, indent=1, sort_keys=True)
    if args.serve_smoke:
        import numpy as np

        ff = _build_tiny_ff()
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 11, 5)]
        want = [ff.generate(p[None, :], max_new_tokens=4)[0]
                for p in prompts]
        server = ff.serve_generation(slots=res.slots, max_len=res.max_len,
                                     serve_strategy=strategy)
        try:
            futs = [server.submit(p, max_new_tokens=4) for p in prompts]
            got = [f.result(timeout=600) for f in futs]
        finally:
            server.stop()
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
    print(json.dumps({
        "serve_strategy": strategy,
        "describe": res.best.describe(),
        "out": args.out,
        "serve_smoke": "token-identical" if args.serve_smoke else None,
    }))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="servesearch", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    se = sub.add_parser("search", help="search the serving-strategy space")
    se.add_argument("--profile", default="smoke")
    se.add_argument("--replay", default=None, metavar="REQLOG_JSONL",
                    help="score against a recorded request log "
                         "(obs.reqlog export; overrides --profile and "
                         "supplies measured prompt/arrival/acceptance "
                         "stats)")
    se.add_argument("--budget", type=int, default=200)
    se.add_argument("--seed", type=int, default=0)
    se.add_argument("--slots", type=int, default=4)
    se.add_argument("--max-len", type=int, default=64)
    se.add_argument("--calibration", default=None,
                    help="fftrace calibrate report (<= 7 days old)")
    se.add_argument("--hbm-budget", type=float, default=None,
                    help="HBM budget in bytes (default: the machine model)")
    se.add_argument("--acceptance-rate", type=float, default=None,
                    help="spec acceptance prior (default: measured from "
                         "--replay's log when it drafted, else 0.6)")
    se.add_argument("--mesh-layouts", default=None,
                    help='candidate meshes, e.g. "data=8;data=2,model=4"')
    se.add_argument("--inner-budget", type=int, default=0,
                    help="mcmc budget per candidate mesh layout")
    se.add_argument("--sim", action="store_true",
                    help="score candidates with the event-driven tick "
                         "simulator (search.ticksim) replaying the "
                         "profile's recorded arrival sequence — needs "
                         "--replay (falls back to closed-form with a "
                         "warning otherwise)")
    se.add_argument("--out", default=None)
    se.set_defaults(func=cmd_search)

    si = sub.add_parser("simulate",
                        help="replay a recorded reqlog through the "
                             "event-driven tick simulator")
    si.add_argument("reqlog", metavar="REQLOG_JSONL",
                    help="obs.reqlog export (server.request_log"
                         ".export_jsonl or fftrace smoke)")
    si.add_argument("--strategy", default=None,
                    help="strategy JSON to simulate (servesearch apply "
                         "--out, or a full search result); default: the "
                         "serve_generation default knobs")
    si.add_argument("--slots", type=int, default=4)
    si.add_argument("--max-len", type=int, default=64)
    si.add_argument("--seed", type=int, default=0)
    si.add_argument("--calibration", default=None,
                    help="fftrace calibrate report (<= 7 days old)")
    si.add_argument("--out", default=None, metavar="TIMELINE_JSON",
                    help="write the per-request TTFT/queue/decode "
                         "timeline JSON")
    si.set_defaults(func=cmd_simulate)

    ex = sub.add_parser("explain", help="break down a search result")
    ex.add_argument("result")
    ex.add_argument("--calibration", default=None,
                    help="fftrace calibrate report: its compile block's "
                         "measured per-compile median prices the "
                         "compile_cost line (default: rough estimate)")
    ex.set_defaults(func=cmd_explain)

    apl = sub.add_parser("apply", help="emit the winning strategy JSON")
    apl.add_argument("result")
    apl.add_argument("--out", default=None)
    apl.add_argument("--serve-smoke", action="store_true",
                     help="serve the strategy on the tiny model and "
                          "assert token identity with dense generate()")
    apl.set_defaults(func=cmd_apply)

    args = ap.parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
