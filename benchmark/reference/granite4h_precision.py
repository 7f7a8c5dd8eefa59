"""Calibrate the tolerance of `granite-4.0-h-micro-serve1`'s comparison
with its reference (`check.tie_tol_sigma` and `check.sample`), on the chip:

    python3 benchmark/reference/granite4h_precision.py --seed <n> \
        --tokens 4096 --sequences 32 --control-sequences 4 --stand-in 4

The server returns tokens, and `benchmark/serving.py` judges each served
token by how far the reference's logit for it lies under the reference's
largest, in standard deviations of the row. This computes that same
statistic for the reference ITSELF run in lower precisions (every matrix
product's operands rounded first, `reference/granite4h.py`
`lower_precision`): bfloat16, the precision the configuration states, and
float8_e4m3fn, the nearest below it, which has to come out as not correct.
It says how many tokens lie beyond each limit (the tails decide how many
tokens a check has to judge before it sees float8) and how far a row's
logits move. The state-space recurrence and its state stay float32 in
every precision, as the program's are. Weights are the program's own draw
from the seed (`families/granite4h.build_server_model`), token ids uniform
over the vocabulary as the traffic draws them. A sequence is 4,096 tokens:
its (4,096, 100,352) float32 logits are 1.6 GB, twice over beside 6.4 GB
of weights.

`--stand-in N` then puts the control through the harness's OWN comparison
(`benchmark/serving.py` `Served.check`, with the configuration's `check`
block as committed): N requests whose tokens the reference generated
greedily with float8 operands, a token at a time, stand in for what a
program computing in float8 would have served; the line `stand_in` of the
result says whether `check` called them correct (it must not).

One JSON object on the last line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LIMITS = ("0.5", "1.0", "1.5", "1.75", "2.0", "2.25", "2.5", "2.75", "3.0",
          "3.25", "3.5", "4.0")
# a check judges about 640 served tokens a sampled request
CHECK_SIZES = (640, 1280, 2560, 3840, 5120, 7680)


def tails(gap):
    """What a limit on the largest gap of N judged tokens would see."""
    n = len(gap)
    beyond = {t: int((gap > float(t)).sum()) for t in LIMITS}
    return {
        "tokens": n,
        "argmax_share": float((gap == 0).mean()),
        "gap_sigma": {q: float(np.quantile(gap, float(q)))
                      for q in ("0.5", "0.9", "0.99", "0.999", "0.9999",
                                "1.0")},
        "tokens_beyond": beyond,
        # the chance that NONE of N judged tokens lies beyond the limit
        "chance_none_beyond": {
            t: {str(size): float((1.0 - beyond[t] / n) ** size)
                for size in CHECK_SIZES}
            for t in ("2.0", "2.25", "2.5", "2.75", "3.0", "3.25", "3.5")},
    }


def stand_in(args, cfg, ff, weights, arch, log):
    """Requests a float8 program would have served, through the harness's
    own `check`."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness, serving, spec
    from benchmark.reference import granite4h as ref

    n_p, n_t = args.stand_in_prompt, args.stand_in_new
    width = -(-(n_p + n_t) // 128) * 128
    dtype = jnp.float8_e4m3fn

    def generate(w, ids):
        def one(p, ids):
            lg = ref.logits(w, ids, arch=arch, operand_dtype=dtype)
            return ids.at[p].set(jnp.argmax(lg[p - 1]).astype(jnp.int32))
        # every layer is causal: what lies at p and after changes no row
        # before p
        return jax.lax.fori_loop(n_p, n_p + n_t, one, ids)

    generate = jax.jit(generate)
    rng = np.random.default_rng(args.seed + 1)
    done = []
    t0 = time.monotonic()
    for i in range(args.stand_in):
        ids = np.zeros((width,), np.int32)
        ids[:n_p] = rng.integers(0, cfg["vocab_size"], n_p, dtype=np.int32)
        out = np.asarray(generate(weights, jnp.asarray(ids)))
        done.append(serving.Request(
            index=i, prompt=out[:n_p].copy(), new_tokens=n_t,
            tokens=out[n_p:n_p + n_t].copy()))
        log(f"stand-in request {i}: {n_t} tokens after "
            f"{time.monotonic() - t0:.0f} s")
    cell = next(c for c in spec.load(ROOT)["cells"].values()
                if c.config_name == args.config)
    chk = cell.config["check"]
    run = harness.Run(
        cell=cell, seed=args.seed, seconds=0.0, trace=False, root=ROOT,
        t_process_start=time.monotonic(), device={},
        compile_clock=harness.CompileClock())
    # what `check` holds the SERVER to besides the tokens is not the
    # stand-in's to show: given as the configuration expects it
    run.extras.update(kernel_variant=chk["kernel_variant"],
                      kv_cache_dtype=chk["kv_cache_dtype"])
    run.counters["steady_state_recompiles"] = 0
    served = object.__new__(serving.Served)
    served.run, served.ff = run, ff
    served.check(done)
    return {"operand_dtype": jnp.dtype(dtype).name, "requests": args.stand_in,
            "prompt_tokens": n_p, "new_tokens": n_t,
            "check": {"sample": chk["sample"],
                      "tie_tol_sigma": chk["tie_tol_sigma"]},
            "correct": bool(run.correct), "why_not": list(run.why_not)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=4096,
                    help="tokens a sequence")
    ap.add_argument("--sequences", type=int, default=1,
                    help="sequences judged in bfloat16")
    ap.add_argument("--control-sequences", type=int, default=None,
                    help="of them, judged in float8 too (default all)")
    ap.add_argument("--stand-in", type=int, default=0,
                    help="requests of the float8 stand-in put through the "
                         "harness's check")
    ap.add_argument("--stand-in-prompt", type=int, default=128)
    ap.add_argument("--stand-in-new", type=int, default=192)
    ap.add_argument("--config", default="granite-4.0-h-micro-serve1")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmark.families import granite4h as fam
    from benchmark.reference import granite4h as ref

    def log(msg):
        print(f"[precision] {msg}", file=sys.stderr, flush=True)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    ff = fam.build_server_model(cfg, args.seed % (2 ** 31 - 1))
    weights = fam.reference_weights(ff._params[0], cfg)
    arch = fam.reference_arch(cfg)

    @jax.jit
    def exact_fn(w, ids):
        lg = ref.logits(w, ids, arch=arch)
        # the head is the table: how far the INPUT token's own logit
        # stands over its row (models/granite4h.py says why it is kept low)
        own = jnp.take_along_axis(lg, ids[:, None], -1)[:, 0]
        return lg, (own - lg.mean(-1)) / lg.std(-1)

    def lowered(dtype):
        def fn(w, ids, exact):
            lg = ref.logits(w, ids, arch=arch, operand_dtype=dtype)
            sigma = exact.std(-1)
            taken = jnp.take_along_axis(exact, lg.argmax(-1)[:, None],
                                        -1)[:, 0]
            return ((exact.max(-1) - taken) / sigma,
                    jnp.abs(lg - exact).max(-1) / sigma)
        return jax.jit(fn)

    names = (("bfloat16", jnp.bfloat16, args.sequences),
             ("float8_e4m3fn", jnp.float8_e4m3fn,
              args.sequences if args.control_sequences is None
              else min(args.control_sequences, args.sequences)))
    fns = {name: lowered(dtype) for name, dtype, _ in names}
    got = {name: {"gap": [], "moved": []} for name, _, _ in names}
    rng = np.random.default_rng(args.seed)
    owns = []
    t0 = time.monotonic()
    for s in range(args.sequences):
        ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], args.tokens,
                                       dtype=np.int32))
        exact, own = exact_fn(weights, ids)
        owns.append(np.asarray(own))
        for name, _, count in names:
            if s >= count:
                continue
            gap, moved = fns[name](weights, ids, exact)
            got[name]["gap"].append(np.asarray(gap))
            got[name]["moved"].append(np.asarray(moved))
        del exact
        log(f"sequence {s}: bfloat16 largest gap "
            f"{got['bfloat16']['gap'][-1].max():.3f} sigma, "
            f"{time.monotonic() - t0:.0f} s")
    own = np.concatenate(owns)
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "tokens": args.tokens, "precisions": {},
           "own_token_logit_sigma": {q: float(np.quantile(own, float(q)))
                                     for q in ("0.1", "0.5", "0.9")}}
    for name, _, count in names:
        g = got[name]
        gap, moved = np.concatenate(g["gap"]), np.concatenate(g["moved"])
        pos = np.tile(np.arange(args.tokens), count)
        row = tails(gap)
        row["sequences"] = count
        row["largest_by_sequence"] = [float(x.max()) for x in g["gap"]]
        # served tokens follow prompts of 128 tokens and more
        row["after_128_tokens"] = {
            "tokens": int((pos >= 128).sum()),
            "largest": float(gap[pos >= 128].max(initial=0.0)),
            "tokens_beyond": {t: int((gap[pos >= 128] > float(t)).sum())
                              for t in LIMITS}}
        row["row_max_logit_move_sigma"] = {
            q: float(np.quantile(moved, float(q)))
            for q in ("0.5", "0.9", "0.99", "0.999", "1.0")}
        out["precisions"][name] = row
    print(json.dumps(out), flush=True)    # kept if the stand-in fails
    if args.stand_in:
        out["stand_in"] = stand_in(args, cfg, ff, weights, arch, log)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
