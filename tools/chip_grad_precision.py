"""What a lowering of the training step does to the gradient, leaf by leaf,
through the chip tool (`--chips 4`).

    python tools/chip_grad_precision.py [--seed N] [--tiny]

Builds the trainer of `benchmark/configs/mistral-7b-train4.json` (its
widths, mesh, strategy, rematerialisation and batch), draws the weights and
ONE batch from the seed, and takes the gradient of the loss once a lowering:

  merged     the step's own forward and backward as the tree lowers them: a
             column-split SwiGLU group per shard (`runtime/column_group.py`),
             each shard's partial input gradients added on the chip and
             reduced over `model` once; a LINEAR's three dots each handing out
             the type `ops/jax_ops.py` `_linear_dot` names for it;
  fallback   the same with `column_group.column_split` answering None: two
             all-reduces a group, placed by the partitioner on the two dots;
  float32-partials, bfloat16-activations, bfloat16-kernel-gradients,
  bfloat16-partials
             `merged` with another `_linear_dot` for the one trace (`standin()`;
             the program has no switch for it), named by what a LINEAR's sums
             cross a mesh axis at. float32-partials is PR 39's tree: every dot
             hands out float32, so the partitioner's all-reduces read float32
             partial sums and round after the sum. bfloat16-activations rounds
             each chip's sum first where an ACTIVATION crosses (forward `down`,
             the head's input gradient: the `model` axis); bfloat16-kernel-
             gradients where a KERNEL's gradient does (the sync over `data`;
             the map is then handed kernels at the activations' dtype, so that
             the sum over its copies reads that dtype); bfloat16-partials both;
  reference  the plain float32 model of `benchmark/reference/` at "highest"
             matmul precision, fed the same weights, a sequence at a time.

Every leaf of every such gradient is compared on the host with the
reference's: ||g - ref|| / ||ref|| a lowering, that error over
float32-partials' (the column a change of what a reduction rounds is judged
by, one group of reductions at a time: PERF.md section 6) and ||merged -
other|| / ||ref||. The table is logged and written to
`chiprun_out/grad_precision.json`. `--tiny` runs the same code at a toy width
(the CPU plumbing check on 4+ virtual devices; the CPU's compiler widens every
bfloat16 dot, so what a rounding costs is the chip's to say). Nothing printed
here is a benchmark: it is the evidence a change of a reduction's dtype owes.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import re
import sys
import time
from unittest import mock

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


def standin(forward, input_grad, kernel_grad):
    """Inside, a step TRACES with `contraction(forward, input_grad,
    kernel_grad)` as every LINEAR's dot, and the map is handed its kernels
    at the dtype their gradients are to be summed at (the masters', or as
    `column_group.handed` has it). A tool's stand-in for one trace, not an
    option of the program."""
    from flexflow_tpu.ops import jax_ops
    from flexflow_tpu.runtime import column_group

    @contextlib.contextmanager
    def lowering():
        with mock.patch.object(jax_ops, "_linear_dot", jax_ops.contraction(
                forward, input_grad, kernel_grad)), \
                mock.patch.object(column_group, "handed", (
                    column_group.handed if kernel_grad is None
                    else lambda w, x: w)):
            yield

    return lowering


def fallback():
    """Inside, no SwiGLU group runs per shard."""
    from flexflow_tpu.runtime import column_group

    return mock.patch.object(column_group, "column_split",
                             lambda graph, mesh, members: None)


F32 = "float32"
BASE = "float32-partials"
LOWERINGS = {
    "merged": contextlib.nullcontext, "fallback": fallback,
    BASE: standin(F32, F32, F32),
    "bfloat16-activations": standin(None, None, F32),
    "bfloat16-kernel-gradients": standin(F32, F32, None),
    "bfloat16-partials": standin(None, None, None)}


def rounded_once(rows, inner, cols, seed):
    """Whether ONE chip's dot that hands out bfloat16 is the float32 dot
    rounded once (the contraction accumulated in float32 whatever the
    result's type: what `contraction` says of a chip), at a forward
    contraction `(rows, inner) @ (inner, cols)` and at a kernel gradient's,
    over the rows: the count of elements that differ, of how many."""
    import jax
    import jax.numpy as jnp

    kx, kw, kg = jax.random.split(jax.random.key(seed % (2 ** 31 - 1)), 3)
    x = jax.random.normal(kx, (rows, inner), jnp.bfloat16)
    w = jax.random.normal(kw, (inner, cols), jnp.bfloat16)
    g = jax.random.normal(kg, (rows, cols), jnp.bfloat16)

    def differing(a, b, dims):
        def dot(a, b, out):
            return jax.lax.dot_general(
                a, b, (dims, ((), ())),
                preferred_element_type=out).astype(jnp.bfloat16)

        dot = jax.jit(dot, static_argnums=2)
        narrow, wide = dot(a, b, jnp.bfloat16), dot(a, b, jnp.float32)
        return int((narrow != wide).sum()), narrow.size

    return {"forward": differing(x, w, ((1,), (0,))),
            "kernel_gradient": differing(x, g, ((0,), (0,)))}


def to_host(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def reduction_lines(compiled, pattern=r"(l0_(gate|up|down)|lm_head)_\d+"):
    """The compiled program's all-reduces under layer 0's linears and the
    head: `result <- operand dtypes  name stack`, for the log."""
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


def field(name: str) -> str:
    return name.replace("-", "_")


def columns(names):
    """The table's columns for these lowerings, in their order."""
    out = ["leaf", "elements", "ref_norm"]
    out += [f"{field(n)}_err" for n in names]
    out += [f"{field(n)}_over_{field(BASE)}" for n in names if n != BASE]
    return out + [f"merged_minus_{field(n)}" for n in names
                  if "merged" in names and n != "merged"]


def compare(named, names):
    """A row of `columns(names)` a leaf, every distance over |ref|, in
    float64 on the host."""
    import numpy as np

    def norm(a):
        return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))

    rows = []
    for leaf, ref in named["reference"].items():
        scale = norm(ref)
        err = {n: norm(named[n][leaf] - ref) / scale for n in names}
        rows.append(
            [leaf, int(ref.size), scale] + [err[n] for n in names]
            + [err[n] / err[BASE] for n in names if n != BASE]
            + [norm(named["merged"][leaf] - named[n][leaf]) / scale
               for n in names if "merged" in names and n != "merged"])
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3907000111)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--lowerings", default=",".join(LOWERINGS),
                    help="which to take, of " + ", ".join(LOWERINGS))
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "grad_precision.json"))
    args = ap.parse_args()
    names = args.lowerings.split(",")
    if not {BASE} <= set(names) <= set(LOWERINGS):
        ap.error(f"--lowerings holds {BASE}, and of {list(LOWERINGS)} alone")

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

    per_chip = t["batch"] * t["seq"] // ex.mesh.shape["data"]
    once = rounded_once(per_chip, cfg["intermediate_size"]
                        // ex.mesh.shape["model"], cfg["hidden_size"],
                        args.seed)
    for k, (differ, of) in once.items():
        log(f"one chip's bfloat16 dot against its float32 dot rounded, {k}: "
            f"{differ} of {of} elements differ")

    host, losses = {}, {}
    layout = jax.tree.map(lambda a: a.sharding, tr)
    for name in names:
        t0 = time.monotonic()
        with LOWERINGS[name]():
            compiled = program_gradient(ex, layout).lower(
                tr, ntr, yb, xb).compile()
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
    cols, rows = columns(names), compare(named, names)
    log(", ".join(cols))
    for r in rows:
        log("%-28s %10d %.6e " % tuple(r[:3])
            + " ".join("%.6e" % v for v in r[3:]))
    for n in names:
        if n != BASE:
            ratio = [r[cols.index(f"{field(n)}_over_{field(BASE)}")]
                     for r in rows]
            worst = rows[int(np.argmax(ratio))][0]
            log(f"{n} / {BASE} error, over {len(rows)} leaves: median "
                f"{float(np.median(ratio)):.4f}, smallest {min(ratio):.4f}, "
                f"largest {max(ratio):.4f} ({worst})")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"seed": args.seed, "batch": t["batch"], "seq": t["seq"],
                   "device": jax.devices()[0].device_kind,
                   "rounded_once": once, "losses": losses,
                   "columns": cols, "rows": rows},
                  f, indent=1)
    ok = all(np.isfinite(r[2:]).all() for r in rows)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
