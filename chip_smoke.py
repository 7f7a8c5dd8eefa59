"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # on a machine with one TPU chip

Drives the two main paths once, through the entry points a user calls, and
fails (non-zero exit, no result line) unless both are right:

  * server:  Llama-3-8B WIDTH (`LlamaConfig.llama3_8b()`: dim 4096, 32 q / 8
    kv heads x 128, hidden 14336, vocab 128256), depth cut to 4 layers —
    1.9 B parameters, 7.7 GB at the fp32 the executor keeps, which with the
    bf16 copies a step makes of them, the page pool and its warm-up copies
    fills most of the 16 GB. `build_llama`
    -> `compile()` -> `serve_generation(paged=True)`; seeded prompts of mixed
    length, some longer than `prefill_chunk`, so chunked prefill and decode
    both run. Checks: every served token is the greedy choice of the dense
    reference (`Executor.decode_fn`, the program `FFModel.generate` runs,
    teacher-forced over prompt + served tokens; a position whose top
    reference logits tie within bf16 noise may pick either);
    `kernel_variant == "ragged_pallas"`; no request failed; zero steady-state
    recompiles after `warm_launch_shapes()`; zero swallowed search failures.
  * trainer: `LlamaConfig.bench_1b()`, batch 8 x seq 1024, remat="hidden",
    bf16 Adam state — the one training shape with chip history (head_dim 128,
    so the flat-lane flash kernels run). The full 8B width does not fit one
    chip with optimizer state: one layer + embedding + head is 1.27 B
    parameters, 15 GB at 12 B/param. Checks: loss finite and lower after the
    steps than before; `LAST_ATTENTION_KERNEL == "pallas_flash"`; every timed
    step ends in a real sync.

A chip belongs to one process at a time, so this parent never imports jax:
each phase is a child (`--phase server|trainer`) that demands
`jax.devices()[0].platform == "tpu"` before anything else, and whose HBM is
released when it exits — the peak each prints is its own. The phases are
importable functions of a size, so tier-1 calls them tiny on the CPU with
interpret mode requested explicitly (tests/test_tpu_smoke.py).

No timing printed here is a benchmark, and nothing here is a claim. The last
stdout line is `{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}` with the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # the whole run, compilation included, fits 1200 s


@dataclasses.dataclass(frozen=True)
class ServerSize:
    llama: dict                      # LlamaConfig fields
    # slots x prefill_chunk size the launch-shape catalog every one of
    # whose programs warm-up compiles (17 at 2 x 16; 39 at the server's
    # default 4 x 64) — kept small so a cold run fits the time limit
    slots: int = 2
    max_len: int = 512
    page_size: int = 64
    # x 64 rows x 16 KiB/row (4 layers) = 256 MiB, and warm-up holds three
    # more pools of this size beside the server's own. At 1024 pages the
    # phase peaked at 15.6 of the chip's 15.75 GiB.
    num_pages: int = 256
    prefill_chunk: int = 16
    prompt_lens: tuple = (5, 23, 64, 150, 301)
    max_new: int = 16


@dataclasses.dataclass(frozen=True)
class TrainerSize:
    llama: dict
    batch: int = 8
    seq: int = 1024
    steps: int = 5


def chip_sizes():
    """The sizes `python chip_smoke.py` runs (module docstring)."""
    from flexflow_tpu.models.llama import LlamaConfig

    server = dataclasses.asdict(LlamaConfig.llama3_8b())
    server["layers"] = 4
    return (ServerSize(llama=server),
            TrainerSize(llama=dataclasses.asdict(LlamaConfig.bench_1b())))


# ---------------------------------------------------------------------------
# phases (run inside the child; importable at any size)


def _search_failures(ff) -> dict:
    """Strategy candidates and cost microbenchmarks that raised and were
    passed over (model.py _validate_candidates, search/measured.py)."""
    return {"failed_candidates": ff.search_stats.get("failed_candidates", 0),
            "failed_measurements":
                ff.search_stats.get("failed_measurements", 0)}


def _check_greedy(ff, prompts, served, tie_tol: float) -> dict:
    """Teacher-forced greedy check: ONE dense pass (Executor.decode_fn, the
    program FFModel.generate prefills with) over prompt + served tokens,
    padded to a common length — causal attention keeps earlier positions
    blind to the padding. Every served token must be the reference argmax,
    or within `tie_tol` standard deviations of the position's logits from
    it (a tie the dtype's rounding may break either way)."""
    import jax.numpy as jnp
    import numpy as np

    ex = ff.executor
    seqs = [np.concatenate([p, t]) for p, t in zip(prompts, served)]
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    tr, ntr = ff._params
    probs, _ = ex.decode_fn()(tr, ntr, ex.init_kv_cache(len(seqs), width), 0,
                              jnp.asarray(ids))
    logits = np.log(np.maximum(np.asarray(probs, np.float32), 1e-38))
    if not np.isfinite(logits).all():
        raise AssertionError("reference logits are not finite")
    exact = ties = 0
    for i, (p, t) in enumerate(zip(prompts, served)):
        for k, tok in enumerate(t):
            row = logits[i, len(p) - 1 + k]
            gap = (row.max() - row[tok]) / max(float(row.std()), 1e-30)
            if gap == 0.0:
                exact += 1
            elif gap <= tie_tol:
                ties += 1
            else:
                raise AssertionError(
                    f"prompt {i} (len {len(p)}) token {k}: served {tok} "
                    f"is {gap:.3f} sigma below the reference argmax "
                    f"{int(row.argmax())}")
    return {"tokens": exact + ties, "exact_argmax": exact,
            "within_tie_tolerance": ties}


def server_phase(size: ServerSize, kv_dtype: str = "auto") -> dict:
    """Serve a few requests through the paged server and check them."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    t0 = time.perf_counter()
    lcfg = LlamaConfig(**size.llama)
    ff = FFModel(FFConfig(batch_size=1, seed=0, num_devices=1))
    build_llama(ff, lcfg, batch_size=1, seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    build_s = time.perf_counter() - t0

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in size.prompt_lens]
    server = ff.serve_generation(
        slots=size.slots, max_len=size.max_len, paged=True,
        page_size=size.page_size, num_pages=size.num_pages,
        prefill_chunk=size.prefill_chunk, kv_dtype=kv_dtype)
    try:
        t0 = time.perf_counter()
        server.warm_launch_shapes()       # every launch shape; marks steady
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        futs = [server.submit(p, max_new_tokens=size.max_new)
                for p in prompts]
        served = [np.asarray(f.result(timeout=600)) for f in futs]
        serve_s = time.perf_counter() - t0
        m = server.metrics()
    finally:
        server.stop()

    for p, t in zip(prompts, served):
        if t.shape != (size.max_new,) or t.min() < 0 \
                or t.max() >= lcfg.vocab_size:
            raise AssertionError(f"prompt len {len(p)}: bad tokens {t}")
    if m["requests_served"] != len(prompts):
        raise AssertionError(f"served {m['requests_served']} of "
                             f"{len(prompts)} requests")
    if m["kernel_variant"] != "ragged_pallas":
        raise AssertionError(f"kernel_variant={m['kernel_variant']}")
    if m["compile"]["steady_state_recompiles"] != 0:
        raise AssertionError(f"steady-state recompiles: {m['compile']}")
    if max(r["prefill_tokens"] for r in m["requests"]) <= size.prefill_chunk:
        raise AssertionError("no prompt was prefilled in chunks")
    failures = _search_failures(ff)
    if any(failures.values()):
        raise AssertionError(f"swallowed search failures: {failures}")
    # int8 pages carry a bounded logit error (docs/paged.md): a token may
    # sit a little below the fp argmax, never far
    greedy = _check_greedy(ff, prompts, served,
                           tie_tol=0.05 if kv_dtype == "auto" else 0.5)
    return {
        "kernel_variant": m["kernel_variant"],
        "kv_cache_dtype": m["kv_cache_dtype"],
        "page_size": size.page_size,
        "greedy_check": greedy,
        "compile_events": m["compile"]["compile_events_total"],
        "compile_seconds_sum": m["compile"]["compile_seconds_sum"],
        "steady_state_recompiles": m["compile"]["steady_state_recompiles"],
        "preemptions": m["preemptions"],
        **failures,
        "wall_s": {"build": round(build_s, 1), "warm": round(warm_s, 1),
                   "serve": round(serve_s, 1)},
    }


def trainer_phase(size: TrainerSize) -> dict:
    """Take a few optimizer steps on one repeated batch and check them."""
    import jax
    import numpy as np

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama
    from flexflow_tpu.ops import jax_ops

    t0 = time.perf_counter()
    lcfg = LlamaConfig(**size.llama)
    ff = FFModel(FFConfig(batch_size=size.batch, seed=0, num_devices=1,
                          remat="hidden"))
    build_llama(ff, lcfg, seq_len=size.seq)
    ff.compile(optimizer=AdamOptimizer(lr=1e-4, state_dtype="bfloat16"),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    build_s = time.perf_counter() - t0

    rs = np.random.RandomState(0)
    x = rs.randint(0, lcfg.vocab_size, (size.batch, size.seq)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    xb, yb = jax.device_put(x), jax.device_put(y)
    step = ff.executor.train_step()
    (tr, ntr), opt = ff._params, ff._opt_state
    rng = jax.random.key(0)
    losses, step_s = [], []
    for _ in range(size.steps):
        t0 = time.perf_counter()
        tr, ntr, opt, metrics = step(tr, ntr, opt, rng, yb, xb)
        losses.append(float(np.asarray(metrics["loss"])))  # the sync
        step_s.append(round(time.perf_counter() - t0, 3))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if jax_ops.LAST_ATTENTION_KERNEL != "pallas_flash":
        raise AssertionError(
            f"attention kernel: {jax_ops.LAST_ATTENTION_KERNEL}")
    failures = _search_failures(ff)
    if any(failures.values()):
        raise AssertionError(f"swallowed search failures: {failures}")
    return {
        "attention_kernel": jax_ops.LAST_ATTENTION_KERNEL,
        "losses": [round(v, 4) for v in losses],
        **failures,
        # step 0 compiles; none of these is a benchmark
        "wall_s": {"build": round(build_s, 1), "steps": step_s},
    }


# ---------------------------------------------------------------------------
# child: one phase, on the chip or not at all


def _child(phase: str, kv_dtype: str) -> int:
    import jax

    from flexflow_tpu import native
    from flexflow_tpu.runtime.compile_cache import (
        CacheCounter,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX attached {device}, not a TPU",
              file=sys.stderr)
        return 3
    import importlib.metadata as md

    print(f"[{phase}] platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']} "
          f"jax={jax.__version__} jaxlib={md.version('jaxlib')} "
          f"libtpu={md.version('libtpu')}", flush=True)
    print(f"[{phase}] search engine: {native.engine()}", flush=True)
    print(f"[{phase}] compile cache: {cache_dir}", flush=True)
    server, trainer = chip_sizes()
    t0 = time.perf_counter()
    out = (server_phase(server, kv_dtype) if phase == "server"
           else trainer_phase(trainer))
    out["phase_wall_s"] = round(time.perf_counter() - t0, 1)
    out["compile_cache"] = {"hits": cache.hits, "misses": cache.misses}
    out["peak_hbm_bytes"] = dev[0].memory_stats()["peak_bytes_in_use"]
    for k, v in out.items():
        print(f"[{phase}] {k}={json.dumps(v)}", flush=True)
    print(json.dumps({"phase": phase, "ok": True, "device": device}),
          flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: never imports jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=("server", "trainer"),
                    help="run one phase in this process (what the parent "
                         "spawns); default: both, each in a child")
    ap.add_argument("--kv-dtype", default="auto",
                    help="server phase pool dtype (int8 with a 32-row page "
                         "multiple is the quantized variant)")
    args = ap.parse_args(argv)
    if args.phase:
        return _child(args.phase, args.kv_dtype)

    t_start = time.monotonic()
    device = None
    for phase in ("server", "trainer"):
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", phase,
             "--kv-dtype", args.kv_dtype],
            stdout=subprocess.PIPE, text=True, cwd=ROOT)
        # a child that hangs, silent or not, is killed at the deadline
        left = DEADLINE_S - (time.monotonic() - t_start)
        watchdog = threading.Timer(max(1.0, left), proc.kill)
        watchdog.start()
        last = ""
        try:
            for line in proc.stdout:
                last = line.strip() or last
                # the child's own result line stays inside the parent
                if not line.startswith('{"phase"'):
                    sys.stdout.write(line)
                    sys.stdout.flush()
            rc = proc.wait()
        finally:
            watchdog.cancel()
        if rc != 0:
            print(f"chip_smoke: phase {phase} failed or ran out of time "
                  f"(rc={rc})", file=sys.stderr)
            return rc if rc > 0 else 4
        result = json.loads(last)
        if not result.get("ok") or result.get("phase") != phase:
            print(f"chip_smoke: phase {phase} printed no result",
                  file=sys.stderr)
            return 5
        device = result["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
