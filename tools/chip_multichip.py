"""The trainer over a four-chip host, through the chip tool (`--chips 4`).

    python tools/chip_multichip.py

Llama-3-8B WIDTH (dim 4096, 32 q / 8 kv heads x 128, hidden 14336, vocab
128256) at 2 layers — 1.49 B parameters, 11.9 GB of fp32 masters and bf16
Adam moments (8 B/param; PSUM sync replicates them over `data`, so each of
four 16 GB chips holds half under mesh {"data": 2, "model": 2}, beside
about 1 GB per f32 copy of its 4096 x 64k logits shard) — batch 8 x seq
1024, three cases, each in its own child so a chip's memory is its case's
alone (this parent never imports jax):

  one_chip  the loss of the freshly initialized model on ONE chip (forward
            only; a training step at this width does not fit one chip), the
            reference the sharded runs must reproduce at the same seed;
  tp        `llama_tp_strategy` (hand Megatron views): a few `fit()` steps;
  search    `search_budget=8` with a two-candidate timed playoff: the
            strategy search prices the attached chip and picks the views.

Checks: every device holds parameter shards and reports memory in use; the
hand-TP step-0 loss matches the one-chip loss within the tolerance the CPU
TP==DP tests use (rtol 2e-3); the loss is finite and falls; no candidate or
microbenchmark failure was swallowed. Exit code 1 otherwise. `--tiny` runs
the same code on Llama-tiny (the CPU plumbing check on 4+ virtual devices).
Nothing printed here is a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MESH = {"data": 2, "model": 2}
STEPS = 3


def _case(case: str, tiny: bool) -> dict:
    import jax
    import numpy as np

    from flexflow_tpu import (
        AdamOptimizer,
        FFConfig,
        FFModel,
        LossType,
        MetricsType,
    )
    from flexflow_tpu.models.llama import (
        LlamaConfig,
        build_llama,
        llama_tp_strategy,
    )
    from flexflow_tpu.ops import jax_ops
    from flexflow_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()
    if tiny:
        lcfg, batch, seq = LlamaConfig.tiny(), 8, 128
    else:
        if dev[0].platform != "tpu" or len(dev) < 4:
            sys.exit(f"needs four TPU chips, JAX attached {len(dev)} x "
                     f"{dev[0].platform}")
        lcfg = dataclasses.replace(LlamaConfig.llama3_8b(), layers=2)
        batch, seq = 8, 1024
    rs = np.random.RandomState(0)
    x = rs.randint(0, lcfg.vocab_size, (batch, seq)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    metrics = [MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY]
    loss_type = LossType.SPARSE_CATEGORICAL_CROSSENTROPY
    t0 = time.perf_counter()
    if case == "one_chip":
        # batch 2 x 4 evaluations: the mean over the same 8 sequences
        ff = FFModel(FFConfig(batch_size=2, seed=0, num_devices=1))
        build_llama(ff, lcfg, seq_len=seq)
        ff.compile(loss_type=loss_type, metrics=metrics)
        pm = ff.eval(x, y, verbose=False)
        losses = [pm.sparse_cce_loss / pm.train_all]
    else:
        cfg = FFConfig(batch_size=batch, seed=0, num_devices=4,
                       mesh_shape=dict(MESH), remat="hidden")
        strategy = None
        if case == "tp":
            strategy = llama_tp_strategy(lcfg)
        else:
            cfg.search_budget = 8
            cfg.validate_top_k = 2
        ff = FFModel(cfg)
        build_llama(ff, lcfg, seq_len=seq)
        ff.compile(optimizer=AdamOptimizer(lr=1e-4, state_dtype="bfloat16"),
                   loss_type=loss_type, metrics=metrics, strategy=strategy)
        losses = []
        for _ in range(STEPS):          # one batch = one step per fit()
            pm = ff.fit(x, y, epochs=1, verbose=False)
            losses.append(pm.sparse_cce_loss / pm.train_all)
    wall = time.perf_counter() - t0
    used = dev[:1] if case == "one_chip" else dev[:4]
    shard_devices = {s.device.id
                     for leaf in jax.tree.leaves(ff._params[0])
                     for s in leaf.addressable_shards}
    mem = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
           for d in used}
    out = {
        "case": case,
        "losses": [round(float(v), 5) for v in losses],
        "param_shard_devices": sorted(shard_devices),
        "bytes_in_use": mem,
        "peak_bytes_in_use": {
            d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in used},
        "attention_kernel": jax_ops.LAST_ATTENTION_KERNEL,
        "failed_candidates": ff.search_stats.get("failed_candidates", 0),
        "failed_measurements": ff.search_stats.get("failed_measurements", 0),
        "wall_s": round(wall, 1),
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev)},
    }
    if case == "search":
        out["search"] = {k: ff.search_stats.get(k) for k in
                         ("wall_s", "best_cost", "baseline_cost")}
        out["strategy_validation"] = {
            k: getattr(ff, "strategy_validation", {}).get(k) for k in
            ("timed_ms", "modeled_ms", "picked_modeled_rank")}
        out["sharded_weights"] = sorted(
            n.name for n in ff.graph.nodes
            if n.sharding is not None and any(
                any(ax for ax in spec)
                for spec in n.sharding.weight_specs.values()))[:8]
    return out


def _check(results: dict) -> list:
    import math

    errors = []
    ref = results["one_chip"]["losses"][0]
    for case in ("tp", "search"):
        r = results[case]
        losses = r["losses"]
        if not all(math.isfinite(v) for v in losses):
            errors.append(f"{case}: loss not finite {losses}")
        # a searched strategy may rewrite the graph (renamed nodes draw
        # other weights at the same seed): only the hand views, which
        # are resharding-only, must reproduce the one-chip loss
        if case == "tp" and not math.isclose(losses[0], ref, rel_tol=2e-3):
            errors.append(f"{case}: step-0 loss {losses[0]} != one-chip "
                          f"{ref} (rtol 2e-3)")
        if not losses[-1] < losses[0]:
            errors.append(f"{case}: loss did not fall {losses}")
        if len(r["param_shard_devices"]) != 4:
            errors.append(f"{case}: parameters on devices "
                          f"{r['param_shard_devices']}")
        if r["device"]["platform"] == "tpu" and not all(
                v and v > 0 for v in r["bytes_in_use"].values()):
            errors.append(f"{case}: a chip reports no memory in use "
                          f"{r['bytes_in_use']}")
        if r["failed_candidates"] or r["failed_measurements"]:
            errors.append(f"{case}: swallowed search failures")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", choices=("one_chip", "tp", "search"))
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.case:
        print(json.dumps(_case(args.case, args.tiny)), flush=True)
        return 0
    results = {}
    for case in ("one_chip", "tp", "search"):
        cmd = [sys.executable, os.path.abspath(__file__), "--case", case]
        proc = subprocess.run(cmd + (["--tiny"] if args.tiny else []),
                              stdout=subprocess.PIPE, text=True, cwd=ROOT)
        if proc.returncode != 0:
            print(f"case {case} failed (rc={proc.returncode})",
                  file=sys.stderr)
            return 1
        results[case] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(json.dumps(results[case]), flush=True)
    errors = _check(results)
    for e in errors:
        print("FAIL", e)
    print(f"{len(errors)} check(s) failed")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
