"""One run of one cell: what every traffic kind shares.

`run_cell` is the whole of a run apart from the demand for a TPU, which
`run.py` makes before it calls this. Tests call it on the CPU at a tiny
size; a number it yields there is never a device metric.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

from benchmark import device as device_mod
from benchmark import xplane
from benchmark.spec import Cell

TRACE_DIR = ".bench_trace"      # inside the checkout, listed in .gitignore
# set-up seconds by jax's own monitoring events: tracing, lowering and the
# backend compile, which on a warm persistent cache is the load from it
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    """An earlier line: anything but the result, which is printed last."""
    print(f"[bench] {msg}", flush=True)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or loading from
    the persistent cache), and cache hits and misses, in this process."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name in COMPILE_EVENTS:
            self.seconds += secs
            if name == COMPILE_EVENTS[-1]:
                self.events += 1

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Run:
    """What a traffic kind is given and what it fills in."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    root: str
    t_process_start: float            # time.monotonic() at process start
    device: Dict
    compile_clock: CompileClock
    # filled by the traffic kind -----------------------------------------
    setup_s: Optional[float] = None
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    why_not: List[str] = dataclasses.field(default_factory=list)
    # for the readers: counts over the window and over set-up, one row per
    # judged request, the program's spans inside the window, the reduced
    # device trace, and whatever else a kind wants to hand its readers
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    requests: List[Dict] = dataclasses.field(default_factory=list)
    spans: List[tuple] = dataclasses.field(default_factory=list)
    reduction: Optional[Dict] = None
    extras: Dict = dataclasses.field(default_factory=dict)

    def family(self):
        return importlib.import_module(
            "benchmark.families." + self.cell.config["family"])

    def program_seed(self) -> int:
        """`--seed` may pass 2**31; the program's seeds are int32."""
        return self.seed % (2 ** 31 - 1)

    def trace_dir(self) -> str:
        return os.path.join(self.root, TRACE_DIR, self.cell.name)


class DeviceTrace:
    """`jax.profiler` over a few seconds of the steady window, and its
    reduction. Only the process that holds the chip can trace it."""

    def __init__(self, run: Run, span_names):
        self.run = run
        self.span_names = tuple(span_names)
        self.dir = run.trace_dir()
        self.started = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans come from TraceMe
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.monotonic()

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.run.extras["traced_s"] = time.monotonic() - self.started

    def reduce(self) -> None:
        path = xplane.find_xplane(self.dir)
        if path is None:
            log("no xplane file was written")
            return
        planes = xplane.read_planes(path)
        self.run.reduction = xplane.reduce_events(
            planes["devices"], planes["host"], span_names=self.span_names)


def read_per_layer(run: Run) -> Dict[str, Dict]:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in run.cell.per_layer:
        args = dict(m.reader)
        mod = importlib.import_module("benchmark.readers." + args.pop("name"))
        value = mod.read(run, **args)
        if value is None:
            log(f"metric {m.name}: nothing to read")
            continue
        out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             root: str, t_process_start: float, device: Dict,
             compile_clock: Optional[CompileClock] = None) -> Dict:
    """Run the cell once and return the contract's result object."""
    run = Run(cell=cell, seed=seed, seconds=float(seconds), trace=trace,
              root=root, t_process_start=t_process_start, device=device,
              compile_clock=compile_clock or CompileClock())
    kind = importlib.import_module(
        "benchmark.traffic_kinds." + cell.traffic["kind"])
    kind.run(run)
    if run.setup_s is None:
        raise RuntimeError(f"traffic kind {cell.traffic['kind']} set no "
                           "setup_s")
    dev = dict(device)
    dev["memory_peak_bytes"] = device_mod.memory_peak_bytes(cell.chips)
    if trace:
        metrics = read_per_layer(run)
        red = run.reduction or {}
        dev["busy_s"] = red.get("busy_s", 0.0)
        dev["window_s"] = red.get("window_s", 0.0)
    else:
        values = dict(run.e2e, setup_s=run.setup_s)
        metrics = {m.name: {"value": float(values[m.name]), "unit": m.unit}
                   for m in cell.end_to_end}
    for reason in run.why_not:
        log(f"not correct: {reason}")
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics, "device": dev}
    if trace and run.reduction:
        result["breakdown"] = {
            "device_ops": run.reduction["device_ops"][:10],
            "idle_gaps": run.reduction["idle_gaps"][:10]}
    return result


def print_result(result: Dict) -> None:
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
