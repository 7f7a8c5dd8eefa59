"""Run one cell of BENCHMARK.json once, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process every time. It demands `platform == "tpu"` and the cell's chip
count before anything is built (there is no CPU fallback), places JAX's
persistent compilation cache through the program's own
`runtime/compile_cache.enable_compile_cache()` (so `JAX_COMPILATION_CACHE_DIR`
is honoured and the fixed path is `<checkout>/.jax_cache`), builds the model
through the program's entry points with weights drawn on the device from
`--seed`, warms this cell's shapes and no other's, measures for `--seconds`,
checks the outputs against the plain reference outside the window, and prints
the result as ONE JSON object on the last line of its standard output. With
`--trace 0` the metrics are the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics (a profiler trace over a few seconds of the window).
Exit code 0 only when a result was printed; `correct` is in the result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import spec

    try:
        cells = spec.load(ROOT)["cells"]
    except spec.SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.workload not in cells:
        print(f"benchmark: no cell {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    try:
        import flexflow_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3

    from benchmark import device, harness

    try:
        dev = device.demand_tpu(cell.chips)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    from flexflow_tpu.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = harness.CompileClock()
    harness.log(f"cell={cell.name} seed={args.seed} seconds={args.seconds} "
                f"trace={args.trace} device={dev} compile_cache={cache_dir}")
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        root=ROOT, t_process_start=T_PROCESS_START, device=dev,
        compile_clock=clock)
    harness.log(f"compile cache hits={clock.hits} misses={clock.misses} "
                f"wall_s={time.monotonic() - T_PROCESS_START:.1f}")
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
