"""What a lowering of the training step does to the gradient, leaf by leaf,
through the chip tool (`--chips 4`).

    python tools/chip_grad_precision.py [--seed N] [--tiny]

Builds the trainer of `benchmark/configs/mistral-7b-train4.json` (its
widths, mesh, strategy, rematerialisation and batch), draws the weights and
ONE batch from the seed, and takes the gradient of the loss three times:

  merged     the step's own forward and backward as the tree lowers them: a
             column-split SwiGLU group per shard (`runtime/column_group.py`),
             each shard's partial input gradients added on the chip, rounded
             to the activations' dtype and reduced over `model` once;
  fallback   the same with `column_group.column_split` answering None, which
             is the parent's lowering: two all-reduces a group, placed by the
             partitioner on the dots' float32 partial sums;
  reference  the plain float32 model of `benchmark/reference/` at "highest"
             matmul precision, fed the same weights, a sequence at a time.

Every leaf of the two bfloat16 gradients is compared on the host with the
reference's: ||g - ref|| / ||ref|| for each lowering and ||merged -
fallback|| / ||ref||. The table is logged and written to
`chiprun_out/grad_precision.json`. `--tiny` runs the same code at a toy
width (the CPU plumbing check on 4+ virtual devices). Nothing printed here
is a benchmark: it is the evidence a change of a reduction's dtype owes.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {
    "family": "mistral", "hidden_size": 256, "intermediate_size": 512,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
    "trainer": {"mesh": {"data": 2, "model": 2},
                "strategy": "llama_tp_strategy", "remat": "hidden",
                "batch": 4, "seq": 128, "lr": 1e-3,
                "adam_state_dtype": "bfloat16"}}


def log(msg: str) -> None:
    print(f"[grad] {msg}", flush=True)


def program_gradient(ex, layout):
    """jit of (trainable, nontrainable, labels, ids) -> (loss, gradient):
    `Executor.train_step()`'s forward and backward without its optimizer,
    the gradient laid out as the parameters are (`layout`)."""
    import jax

    from flexflow_tpu.runtime.loss import compute_loss

    fused = ex.fuse_loss_softmax
    sink_is_sm = ex.last_op_is_softmax and not fused

    def loss_and_gradient(tr, ntr, labels, ids):
        def loss_fn(t):
            logits, _updates, aux = ex.run_forward(
                t, ntr, (ids,), training=True, rng=jax.random.key(0),
                skip_sink_softmax=fused)
            return compute_loss(ex.loss_type, logits, labels,
                                sink_is_sm) + aux

        return jax.value_and_grad(loss_fn)(tr)

    return jax.jit(loss_and_gradient, out_shardings=(None, layout))


def reference_gradient(fam, cfg, weights):
    """jit of (Weights, running sum, ids (S,), labels (S,)) -> (loss, sum +
    Weights of float32 gradients) of one sequence; the sum is updated in
    place. The attention's score blocks are recomputed going back: a
    sequence's would otherwise stay (2 GB a layer at 4096)."""
    import jax

    fam.ref._causal_attention = jax.checkpoint(fam.ref._causal_attention)
    layout = jax.tree.map(lambda a: a.sharding, weights)
    grad = jax.value_and_grad(fam.reference_loss(cfg))

    def accumulate(w, total, ids, labels):
        loss, g = grad(w, ids, labels)
        return loss, jax.tree.map(jax.numpy.add, total, g)

    return jax.jit(accumulate, donate_argnums=1,
                   out_shardings=(None, layout))


def to_host(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def reduction_lines(compiled, pattern=r"l0_(gate|up)_\d+"):
    """The compiled program's all-reduces under layer 0's `gate` / `up`:
    `result <- operand dtypes  name stack`, for the log."""
    text = compiled.as_text()
    made = dict(re.findall(r"^\s*%([\w.\-]+) = \(?(\w+)\[", text, flags=re.M))
    out = []
    for ln in text.splitlines():
        m = re.search(
            r"= \(?(\w+\[[\d,]*\])[^=]*? all-reduce(?:-start)?\(([^)]*)\)", ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if not m or not name or not re.search(pattern, name.group(1)):
            continue
        ops = [made.get(o.split("%")[-1], "?") for o in m.group(2).split(", ")]
        out.append(f"{m.group(1)} <- {','.join(ops)}  {name.group(1)[-90:]}")
    return out


def compare(named, want):
    """[(leaf, elements, |ref|, err merged, err fallback, merged-fallback)]
    with every distance over |ref|, in float64 on the host."""
    import numpy as np

    def norm(a):
        return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))

    rows = []
    for name in want:
        ref, mg, fb = (named[k][name] for k in ("reference", "merged",
                                                "fallback"))
        scale = norm(ref)
        rows.append((name, int(ref.size), scale, norm(mg - ref) / scale,
                     norm(fb - ref) / scale, norm(mg - fb) / scale))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3907000111)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "grad_precision.json"))
    args = ap.parse_args()

    import jax

    if args.tiny:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
        os.environ.setdefault("FF_TPU_FLASH_INTERPRET", "1")
        cfg = json.loads(json.dumps(TINY))
    else:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "mistral-7b-train4.json")) as f:
            cfg = json.load(f)
    import numpy as np

    from benchmark.traffic_kinds.train_steps import batches
    from flexflow_tpu.runtime import column_group

    t = cfg["trainer"]
    fam = importlib.import_module("benchmark.families." + cfg["family"])
    seed = args.seed % (2 ** 31 - 1)
    t0 = time.monotonic()
    ff = fam.build_trainer_model(cfg, seed)
    ff._opt_state = None                    # only the gradient is wanted
    ex = ff.executor
    tr, ntr = ff._params
    x, y = next(batches(args.seed, t["batch"], t["seq"], cfg["vocab_size"]))
    xb, yb = ff._device_put_batch([x, y])
    log(f"device {jax.devices()[0].device_kind} x {jax.device_count()}, "
        f"mesh {dict(ex.mesh.shape)}, batch {t['batch']} x {t['seq']}, "
        f"seed {args.seed}; built in {time.monotonic() - t0:.1f} s")

    host, losses = {}, {}
    layout = jax.tree.map(lambda a: a.sharding, tr)
    split = column_group.column_split
    for name, patched in (("merged", split),
                          ("fallback", lambda graph, mesh, members: None)):
        column_group.column_split = patched
        try:
            t0 = time.monotonic()
            compiled = program_gradient(ex, layout).lower(
                tr, ntr, yb, xb).compile()
        finally:
            column_group.column_split = split
        for ln in reduction_lines(compiled):
            log(f"{name}: all-reduce {ln}")
        loss, grads = compiled(tr, ntr, yb, xb)
        losses[name] = float(loss)
        host[name] = to_host(fam.reference_weights(grads, cfg))
        del grads, compiled
        log(f"{name}: loss {losses[name]:.6f} "
            f"({time.monotonic() - t0:.1f} s with the compile)")

    t0 = time.monotonic()
    weights = fam.reference_weights(tr, cfg)
    one = reference_gradient(fam, cfg, weights)
    total = jax.jit(
        lambda w: jax.tree.map(jax.numpy.zeros_like, w),
        out_shardings=jax.tree.map(lambda a: a.sharding, weights))(weights)
    loss_sum = 0.0
    for b in range(t["batch"]):
        loss, total = one(weights, total, x[b], y[b])
        loss_sum += float(loss)
    losses["reference"] = loss_sum / t["batch"]
    host["reference"] = jax.tree.map(lambda a: a / t["batch"],
                                     to_host(total))
    del total
    log(f"reference: loss {losses['reference']:.6f} "
        f"({time.monotonic() - t0:.1f} s)")

    named = {}
    for k, tree in host.items():
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        named[k] = {jax.tree_util.keystr(p): np.asarray(a) for p, a in flat}
    rows = compare(named, list(named["reference"]))
    log("leaf, elements, |ref|, |merged-ref|/|ref|, |fallback-ref|/|ref|, "
        "|merged-fallback|/|ref|")
    for r in rows:
        log("%-28s %10d %.6e %.6e %.6e %.6e" % r)
    ratio = [r[3] / r[4] for r in rows]
    worst = max(rows, key=lambda r: r[3] / r[4])
    log(f"merged / fallback error, over {len(rows)} leaves: median "
        f"{float(np.median(ratio)):.4f}, largest {max(ratio):.4f} "
        f"({worst[0]})")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"seed": args.seed, "batch": t["batch"], "seq": t["seq"],
                   "device": jax.devices()[0].device_kind,
                   "losses": losses,
                   "columns": ["leaf", "elements", "ref_norm", "merged_err",
                               "fallback_err", "merged_minus_fallback"],
                   "rows": rows}, f, indent=1)
    ok = all(np.isfinite(r[2:]).all() for r in rows)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
