"""`launch_roofline` for a part of the program that is told apart by a
named scope and not by a kernel's name (`scope_share` says how):
`{"name": "scope_roofline", "scope": <regex over the parts of an
operation's name stack>, "shape_fn": <module under benchmark/shape_fns>}`.

The least time of the launches dispatched inside the traced interval, a
launch the sum over its layers of the larger of bytes over the peak
bytes/s and operations over the peak FLOP/s as the shape function counts
them from the launch's `launch_dispatch` span, over the self time of the
operations under the scope there. Never clipped. None where the span
lacks what the shape function reads, or no operation carries the scope.
"""

import importlib

import jax.numpy as jnp

from benchmark import device, tickspans
from benchmark.harness import log
from benchmark.readers import scope_share


def read(run, scope, shape_fn):
    red = run.reduction
    if not red or not red["per_chip"]:
        return None
    launches = [ev for ev in run.spans
                if ev[0] == "launch_dispatch" and ev[4]]
    planes = tickspans.planes(run)
    if not launches or not planes or not planes["devices"]:
        return None
    tied = tickspans.beacon_offset(planes["host"])
    measured = scope_share.seconds(run, scope)
    if tied is None or measured is None:
        return None
    offset = tied[0]
    chip0 = planes["devices"][min(planes["devices"])]
    w0 = min(s for _n, s, _d in chip0)
    w1 = max(s + d for _n, s, d in chip0)
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
    log(f"scope_roofline {shape_fn}: {inside} launches in the traced "
        f"interval; least time {least * 1e3:.2f} ms (bytes alone "
        f"{by_bytes * 1e3:.2f}, operations alone {by_flops * 1e3:.2f}) "
        f"against {measured * 1e3:.2f} ms under the scope")
    return 100.0 * least / measured
