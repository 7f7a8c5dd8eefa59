"""Device time of the operations under a NAMED SCOPE of the program, as a
percentage of the first chip's busy time or of the traced window:
`{"name": "scope_share", "scope": "^hc_mix$", "of": "busy"}`.

`trace_share` tells operations apart by their own names, which serves a
Pallas kernel and nothing else: what XLA compiles of a layer is a run of
fusions called `fusion.<n>`. The program wraps such parts in
`jax.named_scope` (`hc_mix` around the residual mixing, `dsa_index` /
`dsa_select` / `dsa_attend` around the three parts of a sparse latent
layer), the profiler keeps every operation's name stack as the statistic
`tf_op` of the event's metadata (`readers/scope_time.py`, which reads a
training step the same way), and an operation counts here when `scope`
(a regex) matches one WHOLE part of that stack. An operation's time is its
SELF time, so a loop and the operations inside it count once. A fusion
carries one of its instructions' stacks; that is the attribution it gets.

One parse of the xplane file a run, kept in `run.extras`. None, and the
metric is left out: a run without a trace or a TPU plane, or a program
without the scope (no operation matches).
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmark import xplane, xplane_stats
from benchmark.harness import log
from benchmark.readers.scope_time import op_name, self_times

MEMO = "scope_share"


def stacks(run) -> Optional[Dict[str, float]]:
    """{name stack: self seconds} over the first chip's `XLA Ops` line."""
    if MEMO in run.extras:
        return run.extras[MEMO]
    run.extras[MEMO] = None
    path = xplane.find_xplane(run.trace_dir()) if run.trace else None
    if path is None:
        return None
    planes = xplane_stats.read_device_planes(path, lines=(xplane.OPS_LINE,))
    if not planes:
        return None
    ops = planes[min(planes)].lines.get(xplane.OPS_LINE, [])
    own = self_times([(ev.start_ns, ev.duration_ns) for ev in ops])
    out: Dict[str, float] = {}
    for ev, mine in zip(ops, own):
        stack = op_name(ev.stats)
        out[stack] = out.get(stack, 0.0) + mine / 1e9
    run.extras[MEMO] = out
    return out


def seconds(run, scope: str) -> Optional[float]:
    """Self time of the operations one of whose stack's parts `scope`
    matches; None where there is no trace or no such operation."""
    by_stack = stacks(run)
    if not by_stack:
        return None
    rx = re.compile(scope)
    by_part: Dict[str, float] = {}
    for stack, s in by_stack.items():
        part = next((p for p in stack.split("/") if rx.search(p)), None)
        if part is not None:
            by_part[part] = by_part.get(part, 0.0) + s
    if not by_part:
        return None
    if (MEMO, scope) not in run.extras:     # once a scope a run
        run.extras[MEMO, scope] = True
        log(f"scope_share {scope}: " + ", ".join(
            f"{part} {s * 1e3:.2f} ms" for part, s in sorted(
                by_part.items(), key=lambda kv: -kv[1])))
    return sum(by_part.values())


def read(run, scope, of="busy"):
    red = run.reduction
    if not red or not red["per_chip"]:
        return None
    chip = red["per_chip"][min(red["per_chip"])]
    base = chip["busy_s"] if of == "busy" else red["window_s"]
    part = seconds(run, scope)
    if part is None or base <= 0.0:
        return None
    return 100.0 * part / base
