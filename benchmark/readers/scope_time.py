"""Device time of a traced training step by what the program says it is.

The executor stamps named scopes into its step (`flexflow_tpu/obs/scopes.py`:
`forward`, `optimizer`, `step_metrics` around the graph nodes' own keys), JAX
carries them through `grad` and `checkpoint`, and the profiler keeps each
operation's name stack as the statistic `tf_op` of the event's metadata. This
reader opens the run's xplane file a second time (`benchmark/xplane_stats.py`:
`xplane.read_planes` keeps no statistics), takes the first chip's `XLA Ops`
and `XLA Modules` lines and classifies every event through
`obs.scopes.classify`, and a collective besides by the mesh axes that the
replica groups in its own HLO line span (`obs.scopes.group_axes` over the
configuration's `trainer.mesh` in the program's axis order). One parse a run,
kept in `run.extras`; the table is logged as `[bench]` lines. `what` picks
the number:

  `step_device_ms`   median duration of the step program's events on the
                     `XLA Modules` line (the program with the most time)
  `phase_ms`         `phase`'s device time a traced step. An operation's time
                     is its SELF time (its duration less the operations nested
                     in it), so nested operations count once and the phases,
                     `step_metrics` and the unscoped rest add up to busy time
  `unscoped_share`   self time of operations under no phase over busy time, %
  `collective_ms`    union of the collectives whose groups span exactly
                     `axis`, ms a step
  `collective_mb`    their payload a step and chip, MB (hloaudit's byte
                     conventions, from the result shape in the event's line)

None, and the metric is left out: a run without a trace or a TPU plane (the
CPU rehearsal), a mesh of one device (`collective_*`), a program without the
scopes (`phase_ms`, `unscoped_share`: the parent of the PR that added them),
a checkout without `obs/scopes.py` (all but `step_device_ms`).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmark import xplane, xplane_stats
from benchmark.harness import log
from benchmark.stats import median, union_length

MEMO = "scope_time"
OP_NAME_STAT = "tf_op"          # "<jax name stack>:<op type>" on libtpu
_FAMILY = re.compile(r"\.\d+(?= \[|$)")
_DONE_OF = re.compile(r"-done\(.*%([\w.\-]+-start[\w.\-]*)\)")


def op_name(stats: Dict) -> str:
    """The JAX name stack of an event: `tf_op` without its `:<type>`."""
    return str(stats.get(OP_NAME_STAT, "")).rsplit(":", 1)[0]


def self_times(events: List[Tuple[float, float]]) -> List[float]:
    """Each (start, duration)'s duration less the events nested in it, in
    the order given. Events of one line nest or follow one another."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [d for _s, d in events]
    stack: List[int] = []           # indices of the events open now
    for i in order:
        s, d = events[i]
        while stack and sum(events[stack[-1]]) <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= max(0.0, min(s + d, sum(events[p])) - s)
        stack.append(i)
    return own


def node_kind(node_key: Optional[str]) -> str:
    """`l3_attn_norm_41` -> `attn_norm`: a node's key without the layer
    prefix the builders give it and without its guid, so that the same
    node of every layer is one row."""
    if not node_key:
        return "-"
    return re.sub(r"^l\d+_", "", re.sub(r"_\d+$", "", node_key))


def family(name: str) -> str:
    """`%fusion.12 = ...` -> `fusion`, as the breakdown's `device_ops`."""
    return _FAMILY.sub("", xplane.short_name(name))


def build(ops, modules, steps: int, mesh: Dict[str, int], scopes) -> Dict:
    """The whole table from one chip's events (`xplane_stats.StatEvent`).
    Pure: tests feed it hand-built events. `scopes` is the program's
    `flexflow_tpu.obs.scopes`, or None in a checkout without it."""
    out: Dict = {"steps": steps, "phases": None, "collectives": None,
                 "step_device_ms": None}
    by_module: Dict[str, List[float]] = {}
    for ev in modules:
        by_module.setdefault(ev.name, []).append(ev.duration_ns)
    if by_module:
        name = max(by_module, key=lambda n: sum(by_module[n]))
        out["step_module"] = name
        out["step_device_ms"] = median(by_module[name]) / 1e6
    if not ops or not steps:
        return out
    spans = [(ev.start_ns, ev.duration_ns) for ev in ops]
    busy_ns = union_length([(s, s + d) for s, d in spans])
    out["busy_ms_a_step"] = busy_ns / 1e6 / steps
    if scopes is None:
        return out
    own = self_times(spans)
    memo: Dict[str, Tuple] = {}
    phase_ns: Dict[Optional[str], float] = {}
    rows: Dict[Tuple, float] = {}
    coll_iv: Dict[str, List[Tuple[float, float]]] = {}
    coll_bytes: Dict[str, float] = {}
    coll_calls: Dict[Tuple[str, Optional[str]], int] = {}
    groups_of: Dict[str, Optional[list]] = {}
    from flexflow_tpu.analysis import hloaudit

    for ev, mine in zip(ops, own):
        stack = op_name(ev.stats)
        if stack not in memo:
            memo[stack] = scopes.classify(stack)
        phase, node = memo[stack]
        phase_ns[phase] = phase_ns.get(phase, 0.0) + mine
        row = (phase, node_kind(node), family(ev.name))
        rows[row] = rows.get(row, 0.0) + mine
        short = xplane.short_name(ev.name)
        if not xplane.COLLECTIVE.search(short):
            continue
        groups = scopes.collective_groups(ev.name)
        done = _DONE_OF.search(ev.name)
        if groups is None and done:
            groups = groups_of.get(done.group(1))   # the pair's `-start`
        groups_of[short] = groups
        axes = scopes.axes_label(scopes.group_axes(groups, mesh))
        coll_iv.setdefault(axes, []).append(
            (ev.start_ns, ev.start_ns + ev.duration_ns))
        payload = hloaudit.collective_payload(ev.name)
        if payload is not None:     # a `-done` moves nothing of its own
            coll_bytes[axes] = coll_bytes.get(axes, 0.0) + payload[1]
            coll_calls[(axes, phase)] = coll_calls.get((axes, phase), 0) + 1
    scoped = sum(v for k, v in phase_ns.items() if k is not None)
    if scoped > 0.0:
        out["phases"] = {k: v / 1e6 / steps for k, v in phase_ns.items()}
        out["unscoped_share"] = 100.0 * phase_ns.get(None, 0.0) / busy_ns
    out["rows"] = sorted(((v / 1e6 / steps, k) for k, v in rows.items()),
                         key=lambda row: -row[0])
    if mesh:
        out["collectives"] = {
            axes: {"ms": union_length(iv) / 1e6 / steps,
                   "mb": coll_bytes.get(axes, 0.0) / 1e6 / steps}
            for axes, iv in coll_iv.items()}
        out["collective_calls"] = {k: v / steps
                                   for k, v in coll_calls.items()}
    return out


def _log_table(t: Dict, run) -> None:
    log(f"scope_time: step program {t.get('step_module')}: "
        f"{t['step_device_ms']:.3f} ms on the device (median of its "
        f"events); busy {t.get('busy_ms_a_step', 0.0):.3f} ms a step over "
        f"{t['steps']} traced steps")
    if t["phases"] is not None:
        ph = t["phases"]
        total = sum(ph.values())
        log("scope_time: ms a step by phase (self time): " + ", ".join(
            f"{k or 'unscoped'} {v:.3f}" for k, v in sorted(
                ph.items(), key=lambda kv: -kv[1]))
            + f"; sum {total:.3f} against busy {t['busy_ms_a_step']:.3f} "
            f"({100.0 * total / t['busy_ms_a_step']:.2f} %)")
        for ms, (phase, kind, fam) in t["rows"][:10]:
            log(f"scope_time:   {ms:9.3f} ms  {phase or 'unscoped':12s} "
                f"{kind:12s} {fam}")
    elif "rows" in t:
        log("scope_time: no operation carries a scope of the step "
            "(a program compiled without them)")
    if t["collectives"] is not None:
        co = t["collectives"]
        log("scope_time: collectives a step by mesh axes: " + "; ".join(
            f"{axes} {v['ms']:.3f} ms, {v['mb']:.1f} MB"
            for axes, v in sorted(co.items())))
        log("scope_time: collective calls a step by axes and phase: "
            + ", ".join(f"{a}/{p or 'unscoped'} {n:g}" for (a, p), n in
                        sorted(t["collective_calls"].items(),
                               key=lambda kv: str(kv[0]))))
        red = run.reduction
        if red and red.get("per_chip"):
            chip = red["per_chip"][min(red["per_chip"])]
            whole = chip["collective_s"] * 1e3 / t["steps"]
            parts = sum(v["ms"] for v in co.values())
            window = red["window_s"] * 1e3 / t["steps"]
            log(f"scope_time: the axes' collective time adds to "
                f"{parts:.3f} ms a step against {whole:.3f} from the "
                f"reduction that collective_share reads: "
                f"{100.0 * parts / window:.2f} against "
                f"{100.0 * whole / window:.2f} % of the window")


def table(run) -> Optional[Dict]:
    """The run's table, parsed once; None where there is nothing to read."""
    if MEMO in run.extras:
        return run.extras[MEMO]
    run.extras[MEMO] = None
    steps = run.extras.get("traced_steps")
    path = xplane.find_xplane(run.trace_dir()) if run.trace else None
    if not steps or path is None:
        return None
    planes = xplane_stats.read_device_planes(
        path, lines=(xplane.OPS_LINE, "XLA Modules"))
    if not planes:
        return None
    plane = planes[min(planes)]
    try:
        from flexflow_tpu.obs import scopes
    except ImportError:
        scopes = None
    from flexflow_tpu.parallel.mesh import normalize_axes

    mesh = normalize_axes(dict(
        run.cell.config.get("trainer", {}).get("mesh") or {}))
    t = build(plane.lines.get(xplane.OPS_LINE, []),
              plane.lines.get("XLA Modules", []), int(steps), mesh, scopes)
    if t["step_device_ms"] is None:
        return None
    _log_table(t, run)
    run.extras[MEMO] = t
    return t


def read(run, what, phase=None, axis=None):
    t = table(run)
    if t is None:
        return None
    if what == "step_device_ms":
        return t["step_device_ms"]
    if what == "phase_ms":
        return None if t["phases"] is None else t["phases"].get(phase, 0.0)
    if what == "unscoped_share":
        return None if t["phases"] is None else t["unscoped_share"]
    if what in ("collective_ms", "collective_mb"):
        if t["collectives"] is None:
            return None
        return t["collectives"].get(axis, {"ms": 0.0, "mb": 0.0})[what[-2:]]
    raise ValueError(f"scope_time: unknown `what` {what!r}")
