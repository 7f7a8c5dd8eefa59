"""Training steps until the window ends.

Traffic file: `warm_steps` before the window, `in_flight`, how many steps
the host may run ahead of the device, `check_sequences`, how many seeded
sequences the loss is compared on. Batch and sequence length are the
configuration's (`trainer.batch`, `trainer.seq`): the step is compiled for
them.

Each step gets a fresh batch made on the host from the seed and put on the
device (the program's own `_device_put_batch`, which `fit()` uses), losses
stay on the device and are read after the window. There is a sync at each
end of the window and none forced per step: the host only waits for the
step `in_flight` behind the one it is sending, so that the queue, and with
it the overrun of the window, stays bounded.

End to end: `train_tok_s`, tokens of the steps started inside the window
over the time from the window's start to the sync that ends the last of
them, all chips together.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import DeviceTrace, Run, log
from benchmark.stats import median

TRACE_STEPS = 3


def batches(seed: int, batch: int, seq: int, vocab: int):
    """An endless stream of (ids, labels) host batches, a pure function of
    the seed; labels are the ids moved on by one."""
    rng = np.random.default_rng(seed)
    while True:
        x = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        yield x, np.roll(x, -1, axis=1)


def run(run: Run) -> None:
    import jax
    import jax.numpy as jnp

    traffic, cfg = run.cell.traffic, run.cell.config
    t = cfg["trainer"]
    batch, seq, vocab = t["batch"], t["seq"], cfg["vocab_size"]
    fam = run.family()
    t_build = time.monotonic()
    ff = fam.build_trainer_model(cfg, run.program_seed())
    log(f"model built in {time.monotonic() - t_build:.1f} s")
    step = ff.executor.train_step()
    (tr, ntr), opt = ff._params, ff._opt_state
    rng = jax.random.key(run.program_seed())
    stream = batches(run.seed, batch, seq, vocab)
    losses = []

    def one_step():
        nonlocal tr, ntr, opt, rng
        x, y = next(stream)
        xb, yb = ff._device_put_batch([x, y])
        rng, sub = jax.random.split(rng)
        tr, ntr, opt, m = step(tr, ntr, opt, sub, yb, xb)
        losses.append(m["loss"])

    t_warm = time.monotonic()
    for _ in range(int(traffic["warm_steps"])):
        one_step()
    jax.block_until_ready(losses)
    log(f"{len(losses)} warm steps in {time.monotonic() - t_warm:.1f} s")
    n_warm = len(losses)
    in_flight = int(traffic["in_flight"])
    trace = None
    if run.trace:
        trace = DeviceTrace(run, ("train_step",))

    t0 = time.monotonic()
    run.setup_s = t0 - run.t_process_start
    run.counters["setup_compile_s"] = run.compile_clock.seconds
    events_before = run.compile_clock.events
    done_t = []
    while time.monotonic() - t0 < run.seconds:
        k = len(losses) - n_warm
        if trace is not None and k == in_flight + 1:
            jax.block_until_ready(losses)   # the trace holds whole steps
            trace.start()
        if trace is not None and k == in_flight + 1 + TRACE_STEPS:
            jax.block_until_ready(losses)
            trace.stop()
        one_step()
        if len(losses) - n_warm > in_flight:
            losses[-1 - in_flight].block_until_ready()
            done_t.append(time.monotonic())
    jax.block_until_ready(losses)
    t_end = time.monotonic()
    if trace is not None and trace.started is not None \
            and "traced_s" not in run.extras:
        trace.stop()
    steps = len(losses) - n_warm
    run.counters["window_compile_events"] = (run.compile_clock.events
                                             - events_before)
    run.counters["steps"] = steps
    run.attempted, run.failed = steps, 0
    tok_s = steps * batch * seq / (t_end - t0)
    run.e2e["train_tok_s"] = tok_s
    host = np.asarray(jnp.stack(losses), np.float32)
    log(f"{steps} steps of {batch} x {seq} tokens in {t_end - t0:.2f} s: "
        f"{tok_s:.1f} tokens/s; loss {host[n_warm]:.4f} -> {host[-1]:.4f}")
    step_s = np.diff(done_t)
    if len(step_s):
        run.extras["step_ms"] = float(median(step_s)) * 1e3
        log(f"median time between step completions "
            f"{run.extras['step_ms']:.2f} ms")
    run.extras.update(
        train_tok_s=tok_s,
        flops_per_token=fam.train_flops_per_token(cfg, seq))
    if trace is not None:
        trace.reduce()
        run.extras["traced_steps"] = TRACE_STEPS

    del opt
    run.why_not.extend(_wrong(run, ff, tr, ntr, host[n_warm:]))
    run.correct = not run.why_not


def _wrong(run: Run, ff, tr, ntr, losses) -> list:
    """Outside the window: every reason the run is not correct."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import jax_ops

    cfg, traffic = run.cell.config, run.cell.traffic
    chk, fam = cfg["check"], run.family()
    batch, seq, vocab = (cfg["trainer"]["batch"], cfg["trainer"]["seq"],
                         cfg["vocab_size"])
    why = []
    if not np.isfinite(losses).all():
        why.append("a loss is not finite")
    elif not losses[-1] < losses[0]:
        why.append(f"loss did not fall over the window: {losses[0]} -> "
                   f"{losses[-1]}")
    if not str(jax_ops.LAST_ATTENTION_KERNEL).startswith(
            chk["attention_kernel"]):
        why.append(f"attention kernel {jax_ops.LAST_ATTENTION_KERNEL}")
    if run.counters["window_compile_events"]:
        why.append("something compiled inside the window")
    stats = getattr(ff, "search_stats", {}) or {}
    if stats.get("failed_candidates") or stats.get("failed_measurements"):
        why.append(f"swallowed search failures: {stats}")
    # the program's forward loss on a few seeded sequences, repeated to
    # fill the batch the step was compiled for, against the reference's
    # mean over the same sequences, both on the trained weights
    n_chk = int(traffic["check_sequences"])
    rs = np.random.default_rng(run.seed + 7)
    seqs = rs.integers(0, vocab, (n_chk, seq), dtype=np.int32)
    labels = np.roll(seqs, -1, axis=1)
    reps = -(-batch // n_chk)
    x = np.tile(seqs, (reps, 1))[:batch]
    y = np.tile(labels, (reps, 1))[:batch]
    if batch % n_chk:
        why.append("check_sequences must divide the batch")
    xb, yb = ff._device_put_batch([x, y])
    prog = float(np.asarray(ff.executor.eval_step()(tr, ntr, yb, xb)["loss"]))
    weights = fam.reference_weights(tr, cfg)
    loss_fn = jax.jit(fam.reference_loss(cfg))
    ref = float(np.mean([np.asarray(loss_fn(weights, jnp.asarray(s),
                                            jnp.asarray(lb)))
                         for s, lb in zip(seqs, labels)]))
    rel = abs(prog - ref) / abs(ref)
    log(f"forward loss on {n_chk} seeded sequences: program {prog:.6f}, "
        f"reference {ref:.6f}, relative difference {rel:.2e} "
        f"(tolerance {chk['loss_rtol']})")
    if not rel <= float(chk["loss_rtol"]):
        why.append(f"program loss {prog} differs from the reference's {ref}")
    return why
