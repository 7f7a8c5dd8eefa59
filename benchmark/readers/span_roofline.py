"""The ragged attention kernel's share of its roofline over the traced
interval, from what each launch had to walk (device trace joined with the
program's `launch_dispatch` spans by the clock beacons).

`{"name": "span_roofline", "pattern": <regex over operation names>}`. A
`launch_dispatch` span carries, counted at the launch, `kv_pages` (pages of
K/V the items with work reach over) and `qk_pairs` (causal query-key pairs).
The least time of one launch, per layer, is the larger of

    kv_pages * page_size * Hkv * D * 2 (K and V) * itemsize / HBM bytes/s
    4 * qk_pairs * Hq * D                                   / bf16 FLOP/s

summed over the launches dispatched inside the traced interval (their
stamps moved onto the profiler's clock by `tickspans.beacon_offset`) and
over the layers, against the device time of the operations matching
`pattern` there. Queries, new K/V rows and the output are left out of the
bytes (a few rows against whole pages). Never clipped.
"""

import jax.numpy as jnp

from benchmark import device, tickspans, xplane
from benchmark.harness import log


def read(run, pattern):
    red = run.reduction
    if not red or not red["per_chip"]:
        return None
    launches = [ev for ev in run.spans
                if ev[0] == "launch_dispatch" and ev[4]
                and "kv_pages" in ev[4]]
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
    cfg = run.cell.config
    peaks = device.peaks(run.device["kind"])
    itemsize = jnp.dtype(run.extras["kv_cache_dtype"]).itemsize
    page_bytes = (cfg["server"]["page_size"] * cfg["num_key_value_heads"]
                  * cfg["head_dim"] * 2 * itemsize)
    pair_flops = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    least = by_bytes = by_flops = 0.0
    inside = bytes_bound = 0
    for _name, t0, _dur, _tid, attrs in launches:
        if not w0 <= t0 + offset <= w1:
            continue
        tb = attrs["kv_pages"] * page_bytes / peaks["hbm_bytes_per_s"]
        tf = attrs["qk_pairs"] * pair_flops / peaks["bf16_flops_per_s"]
        inside += 1
        bytes_bound += tb >= tf
        by_bytes += tb
        by_flops += tf
        least += max(tb, tf)
    if not inside:
        return None
    layers = cfg["num_hidden_layers"]
    log(f"span_roofline: {inside} launches in the traced interval, "
        f"{bytes_bound} bound by bytes; least time over {layers} layers "
        f"{layers * least * 1e3:.2f} ms (bytes alone "
        f"{layers * by_bytes * 1e3:.2f}, operations alone "
        f"{layers * by_flops * 1e3:.2f}) against {measured * 1e3:.2f} ms "
        "of kernel time")
    return 100.0 * layers * least / measured
