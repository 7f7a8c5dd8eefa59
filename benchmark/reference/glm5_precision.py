"""Calibrate the tolerance of `glm-5.3-flash-serve1`'s comparison with its
reference (`check.tie_tol_sigma` and `check.sample`), on the chip:

    python3 benchmark/reference/glm5_precision.py --seed <n> \
        --tokens 16384 --sequences 8 --control-sequences 3 --stand-in 7

`benchmark/reference/ling3_precision.py` says what is computed and why
(the statistic `Served.check` computes, for the reference ITSELF with
every matrix product's operands rounded to bfloat16, the precision the
configuration states, and to float8_e4m3fn, the nearest below it, against
itself in float32; then `--stand-in N` requests that the float8 reference
generated greedily, put through `Served.check` under the configuration's
own `check` block: it must call them not correct). This is that script
for the GLM-5.3-Flash family: `families/glm5.py`, `reference/glm5.py`,
whose lower precisions round the residual streams as stored too, and keep
the mixing's coefficients, the router's product and the delta-rule
recurrence float32 as the program does. TWO discrete choices flip on
rounding here, a token's 8 of 288 experts and a row's 511 of thousands of
blocks, so the tails are longer than Ling-3.0-flash's; the choice of
experts is reported a layer as there (what `routes` collects).

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
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference.ling3_precision import (  # noqa: E402,F401
    CHECK_SIZES,
    LIMITS,
    tails,
)

def stand_in(args, cfg, ff, weights, arch, log):
    """Requests a float8 program would have served, through the harness's
    own `check`."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness, serving, spec
    from benchmark.reference import glm5 as ref

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
    ap.add_argument("--stand-in-prompt", type=int, default=320)
    ap.add_argument("--stand-in-new", type=int, default=192)
    ap.add_argument("--config", default="glm-5.3-flash-serve1")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmark.families import glm5 as fam
    from benchmark.reference import glm5 as ref

    def log(msg):
        print(f"[precision] {msg}", file=sys.stderr, flush=True)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    ff = fam.build_server_model(cfg, args.seed % (2 ** 31 - 1))
    weights = fam.reference_weights(ff._params[0], cfg)
    arch = fam.reference_arch(cfg)
    lo, hi = cfg["experts_held"]

    @jax.jit
    def exact_fn(w, ids):
        routes = []
        lg = ref.logits(w, ids, arch=arch, routes=routes)
        return lg, jnp.stack(routes)

    def lowered(dtype):
        def held(r):
            # a differing choice matters here only if it touches a held
            # expert
            return jnp.sort(jnp.where((r >= lo) & (r < hi), r, -1), -1)

        def fn(w, ids, exact, routes):
            rt = []
            lg = ref.logits(w, ids, arch=arch, operand_dtype=dtype,
                            routes=rt)
            rt = jnp.stack(rt)
            sigma = exact.std(-1)
            taken = jnp.take_along_axis(exact, lg.argmax(-1)[:, None],
                                        -1)[:, 0]
            same = (jnp.sort(rt, -1) == jnp.sort(routes, -1)).all(-1)
            same_held = (held(rt) == held(routes)).all(-1)
            return ((exact.max(-1) - taken) / sigma,
                    jnp.abs(lg - exact).max(-1) / sigma,
                    1.0 - same.mean(-1), 1.0 - same_held.mean(-1))
        return jax.jit(fn)

    names = (("bfloat16", jnp.bfloat16, args.sequences),
             ("float8_e4m3fn", jnp.float8_e4m3fn,
              args.sequences if args.control_sequences is None
              else min(args.control_sequences, args.sequences)))
    fns = {name: lowered(dtype) for name, dtype, _ in names}
    got = {name: {"gap": [], "moved": [], "differs": [], "held": []}
           for name, _, _ in names}
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for s in range(args.sequences):
        ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], args.tokens,
                                       dtype=np.int32))
        exact, routes = exact_fn(weights, ids)
        for name, _, count in names:
            if s >= count:
                continue
            gap, moved, differs, held = fns[name](weights, ids, exact,
                                                  routes)
            g = got[name]
            g["gap"].append(np.asarray(gap))
            g["moved"].append(np.asarray(moved))
            g["differs"].append(np.asarray(differs))
            g["held"].append(np.asarray(held))
        del exact, routes
        log(f"sequence {s}: bfloat16 largest gap "
            f"{got['bfloat16']['gap'][-1].max():.3f} sigma, "
            f"{time.monotonic() - t0:.0f} s")
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "tokens": args.tokens, "precisions": {}}
    for name, _, count in names:
        g = got[name]
        gap, moved = np.concatenate(g["gap"]), np.concatenate(g["moved"])
        pos = np.tile(np.arange(args.tokens), count)
        row = tails(gap)
        row["sequences"] = count
        row["largest_by_sequence"] = [float(x.max()) for x in g["gap"]]
        # served tokens follow prompts of thousands of tokens
        row["after_1024_tokens"] = {
            "tokens": int((pos >= 1024).sum()),
            "largest": float(gap[pos >= 1024].max(initial=0.0)),
            "tokens_beyond": {t: int((gap[pos >= 1024] > float(t)).sum())
                              for t in LIMITS}}
        row["row_max_logit_move_sigma"] = {
            q: float(np.quantile(moved, float(q)))
            for q in ("0.5", "0.9", "0.99", "0.999", "1.0")}
        row["top8_differs_share_by_layer"] = [
            float(x) for x in np.mean(g["differs"], axis=0)]
        row["held_top8_differs_share_by_layer"] = [
            float(x) for x in np.mean(g["held"], axis=0)]
        out["precisions"][name] = row
    print(json.dumps(out), flush=True)    # kept if the stand-in fails
    if args.stand_in:
        out["stand_in"] = stand_in(args, cfg, ff, weights, arch, log)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
