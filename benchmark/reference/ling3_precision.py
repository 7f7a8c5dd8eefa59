"""Calibrate the tolerance of `ling-3-flash-serve1`'s comparison with its
reference (`check.tie_tol_sigma`), on the chip:

    python3 benchmark/reference/ling3_precision.py --seed <n> --tokens 4096

The server returns tokens, and `benchmark/serving.py` judges each served
token by how far the reference's logit for it lies under the reference's
largest, in standard deviations of the row. This computes that same
statistic for the reference ITSELF run in lower precisions (every matrix
product's operands rounded first, `reference/ling3.py`
`lower_precision`): bfloat16, the precision the configuration states, and
float8_e4m3fn, the nearest below it, which has to come out as not correct.
It also says how often a token's top-8 experts differ from the float32
reference's in each expert layer, and how far a row's logits move. The
delta-rule recurrence and the router's product stay float32 in every
precision, as the program's state and router are. Weights are the
program's own draw from the seed (`families/ling3.build_server_model`),
token ids uniform over the vocabulary slice as the traffic draws them. One
JSON object on the last line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--config", default="ling-3-flash-serve1")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import ling3 as fam
    from benchmark.reference import ling3 as ref

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    ff = fam.build_server_model(cfg, args.seed % (2 ** 31 - 1))
    weights = fam.reference_weights(ff._params[0], cfg)
    arch = fam.reference_arch(cfg)
    ids = jnp.asarray(np.random.default_rng(args.seed).integers(
        0, cfg["vocab_size"], args.tokens, dtype=np.int32))

    def run(dtype):
        def fn(w, ids):
            routes = []
            lg = ref.logits(w, ids, arch=arch, operand_dtype=dtype,
                            routes=routes)
            return lg, jnp.stack(routes)
        return jax.jit(fn)(weights, ids)

    exact, routes = run(None)
    sigma = exact.std(-1)
    top = exact.max(-1)
    lo, hi = cfg["experts_held"]
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "tokens": args.tokens, "precisions": {}}
    for name, dtype in (("bfloat16", jnp.bfloat16),
                        ("float8_e4m3fn", jnp.float8_e4m3fn)):
        lg, rt = run(dtype)
        taken = jnp.take_along_axis(exact, lg.argmax(-1)[:, None], -1)[:, 0]
        gap = np.asarray((top - taken) / sigma)
        moved = np.asarray(jnp.abs(lg - exact).max(-1) / sigma)
        same = (jnp.sort(rt, -1) == jnp.sort(routes, -1)).all(-1)  # (L, S)
        held = lambda r: ((r >= lo) & (r < hi))
        # a differing choice matters here only if it touches a held expert
        same_held = (jnp.sort(jnp.where(held(rt), rt, -1), -1)
                     == jnp.sort(jnp.where(held(routes), routes, -1), -1)
                     ).all(-1)
        out["precisions"][name] = {
            "argmax_share": float((gap == 0).mean()),
            "gap_sigma": {q: float(np.quantile(gap, float(q)))
                          for q in ("0.5", "0.9", "0.99", "0.999", "1.0")},
            "tokens_beyond": {t: int((gap > float(t)).sum())
                              for t in ("0.15", "0.3", "0.5", "1.0", "1.5",
                                        "2.0")},
            "row_max_logit_move_sigma": {
                q: float(np.quantile(moved, float(q)))
                for q in ("0.5", "0.9", "0.99", "1.0")},
            "top8_differs_share_by_layer": [
                float(1.0 - s.mean()) for s in same],
            "held_top8_differs_share_by_layer": [
                float(1.0 - s.mean()) for s in same_held],
        }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
