"""A kernel's share of its roofline over the traced interval, from what
each launch had to do as the program counted it on its `launch_dispatch`
span (device trace joined with the spans by the clock beacons, as
`span_roofline` joins them).

`{"name": "launch_roofline", "pattern": <regex over operation names>,
"shape_fn": <module under benchmark/shape_fns>}`. The shape function's
`per_launch(attrs, cfg, itemsize)` gives, for ONE launch, a list with one
(bytes, operations) pair a layer, or None where the span lacks what it
reads (a program without the counter: the metric is then left out). The
least time of a launch is the sum over its layers of the larger of bytes
over the peak bytes/s and operations over the peak FLOP/s; the share is
the sum over the launches dispatched inside the traced interval over the
device time of the operations matching `pattern` there. Never clipped.
"""

import importlib

import jax.numpy as jnp

from benchmark import device, tickspans, xplane
from benchmark.harness import log


def read(run, pattern, shape_fn):
    red = run.reduction
    if not red or not red["per_chip"]:
        return None
    launches = [ev for ev in run.spans
                if ev[0] == "launch_dispatch" and ev[4]]
    planes = tickspans.planes(run)
    if not launches or not planes or not planes["devices"]:
        return None
    tied = tickspans.beacon_offset(planes["host"])
    if tied is None:
        return None
    offset = tied[0]
    chip0 = planes["devices"][min(planes["devices"])]
    w0 = min(s for _n, s, _d in chip0)
    w1 = max(s + d for _n, s, d in chip0)
    measured = xplane.time_matching(red, pattern)
    if measured <= 0.0:
        return None
    fn = importlib.import_module("benchmark.shape_fns." + shape_fn)
    peaks = device.peaks(run.device["kind"])
    itemsize = jnp.dtype(run.extras["kv_cache_dtype"]).itemsize
    least = by_bytes = by_flops = 0.0
    inside = 0
    for _name, t0, _dur, _tid, attrs in launches:
        if not w0 <= t0 + offset <= w1:
            continue
        need = fn.per_launch(attrs, run.cell.config, itemsize)
        if need is None:
            continue
        inside += 1
        for nbytes, flops in need:
            tb = nbytes / peaks["hbm_bytes_per_s"]
            tf = flops / peaks["bf16_flops_per_s"]
            by_bytes += tb
            by_flops += tf
            least += max(tb, tf)
    if not inside:
        return None
    log(f"launch_roofline {shape_fn}: {inside} launches in the traced "
        f"interval; least time {least * 1e3:.2f} ms (bytes alone "
        f"{by_bytes * 1e3:.2f}, operations alone {by_flops * 1e3:.2f}) "
        f"against {measured * 1e3:.2f} ms of kernel time")
    return 100.0 * least / measured
