"""A kernel's share of its roofline, from the device trace.

`{"name": "kernel_roofline", "pattern": <regex over operation names>,
"shape_fn": <module under benchmark/shape_fns>}`. The shape function gives
the operations and bytes ONE chip's kernels need for one traced step at the
cell's static shapes; the least time is the larger of operations over the
peak FLOP/s and bytes over the peak bytes/s; the share is that over the
kernels' measured device time per step on the first chip. Never clipped: a
share over 100 % means the count is too high or the time leaves work out.
"""

import importlib

from benchmark import device, xplane


def read(run, pattern, shape_fn):
    red = run.reduction
    steps = run.extras.get("traced_steps")
    if not red or not red["per_chip"] or not steps:
        return None
    measured = xplane.time_matching(red, pattern) / steps
    if measured <= 0.0:
        return None
    fn = importlib.import_module("benchmark.shape_fns." + shape_fn)
    need = fn.per_chip_step(run.cell.config)
    peaks = device.peaks(run.device["kind"])
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / measured
