"""Device time of a traced SERVING launch by what the program says each
operation is for.

The ragged step wraps every graph node's own scope in its GROUP (`attn`,
`ffn`, `experts`, `state`, `head`, `glue`), an attention node names its four
PARTS (`qkv`, `kv_write`, `attend`, `out`) and the step's work outside its
nodes is under `unpack` (`flexflow_tpu/obs/scopes.py` `classify_serving`,
the one reader of a name stack). This reader takes chip 0's `XLA Ops` and
`XLA Modules` lines of the traced interval (`benchmark/xplane_stats.py`,
the events WITH their metadata's `tf_op`), and splits the chip's busy time:

  * an operation's time is its SELF time (`scope_time.self_times`), so a
    loop and what runs inside it count once and the parts add up to busy;
  * an operation belongs to the PROGRAM whose event on `XLA Modules` holds
    its start. The step program is the one with the most time; every event
    of that name (`jit_step(<n>)`, one `<n>` a launch shape) is a LAUNCH.
    What runs in any other program (the sampling programs of a decode
    tick) is `other programs`;
  * an operation of the step without a name stack (a compiler-made copy,
    an asynchronous `-done`) takes the stack of the `-start` its HLO line
    names, else of the first operand named in its line that has one, looked
    up among the instructions of the same program. Else, where its line
    (or its `-start`'s) names a PARAMETER of a graph node (XLA names a
    parameter after its path in the step's arguments,
    `trainable__<node key>____<leaf>__`; the node keys are the ones the
    trace's own stacks name), it takes the stack of that node's next
    operation in time: the compiler prefetches a weight into fast memory
    with a stackless `copy-start` / `slice-start` and waits for it in a
    `-done` just before its first use, and that wait is charged to the
    node and part that reads the weight. What is still without a stack is
    `unscoped`;
  * a LAYOUT operation only moves or re-lays data. Decided by the HLO line
    alone: its opcode is one of `LAYOUT_OPCODES`, or it is a fusion whose
    name XLA made of nothing but `LAYOUT_WORDS` (`bitcast_bitcast_fusion`,
    `copy_bitcast_fusion`; XLA names a TPU fusion after the opcodes it
    holds, and a plain `fusion.<n>` holds arithmetic). A Pallas custom call
    is `kernel`, everything else `other`.

The whole table is logged once a run as `[bench] serve_scope` lines.
`read(run, what, ...)` picks one number:

  `node_ms`       `group`'s self time over launches, ms
  `attn_part_ms`  `part`'s, within the group `attn`; the four add up to it
  `layout_ms`     the layout operations' self time over launches, whatever
                  their group: what ROADMAP S10 asks for
  `unscoped_share`  the step's operations under no group over busy time, %:
                  the split's blind spot
  `programs_per_launch`  events on `XLA Modules` over the step program's

None, and the metric is left out: a run without a trace or a TPU plane; a
checkout without `classify_serving` or a step compiled without the groups
(the parent of the PR that added them: only `layout_ms` and
`programs_per_launch`, which need no scope, are read there). A step whose
operations name graph nodes and no group is logged loudly: an executable
from before the groups, handed back by a compile cache whose key holds no
metadata (docs/observability.md, "The serving step's cache key").

One parse of the xplane file, shared with `scope_share` when this reader
runs first; it runs after the measured window and adds a second or two of
wall time to a traced run, nothing to an untraced one.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from benchmark import xplane, xplane_stats
from benchmark.harness import log
from benchmark.readers import scope_share
from benchmark.readers.scope_time import family, node_kind, op_name, \
    self_times
from benchmark.stats import union_length

MEMO = "serve_scope"
MODULES_LINE = "XLA Modules"
OTHER_PROGRAMS, UNSCOPED = "other programs", "unscoped"
LAYOUT_OPCODES = frozenset({
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "transpose", "reshape", "slice"})
LAYOUT_WORDS = frozenset({"bitcast", "copy", "transpose", "reshape",
                          "slice"})
# the opcode follows the result type, which ends `]`, `}` or `)`
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_NAMED = re.compile(r"%([\w.\-]+)")
_DONE_OF = re.compile(r"-done\(.*%([\w.\-]+-start[\w.\-]*)\)")
_RESULT = re.compile(r" = (\(?[a-z]\w*\[[\d,]*\][^ ]*)")
_PROGRAM = re.compile(r"\(\d+\)$")


def opcode(line: str) -> str:
    m = _OPCODE.search(line)
    return m.group(1) if m else ""


def kind(line: str) -> str:
    """`kernel`, `layout` or `other` of one event's HLO line."""
    if "tpu_custom_call" in line:
        return "kernel"
    op = opcode(line)
    if op in LAYOUT_OPCODES:
        return "layout"
    words = family(line).split("_")
    if op == "fusion" and words[-1] == "fusion" and len(words) > 1 \
            and all(w in LAYOUT_WORDS for w in words[:-1]):
        return "layout"
    return "other"


def describe(line: str) -> Tuple[str, str]:
    """(`<result shape> <- <first operand's shape>`, that operand's name)
    of an HLO line; shapes with their layouts."""
    res, op = _RESULT.search(line), _OPCODE.search(line)
    arg = _NAMED.search(line, op.end()) if op else None
    shape = line[op.end():arg.start()].strip() if arg else ""
    return (f"{res.group(1) if res else '?'} <- {shape}",
            "%" + arg.group(1) if arg else "")


def _stacks(ops, program_of, node_of) -> List[str]:
    """Each event's name stack, its own or the one it inherits (module
    docstring), in the order given. `node_of(stack)` is the graph node a
    stack names, or None."""
    def instr(ev):
        return xplane.short_name(ev.name).split(" [")[0]

    own: Dict[Tuple, str] = {}
    lines: Dict[Tuple, str] = {}
    by_node: Dict[str, List[Tuple[float, str]]] = {}
    for ev, prog in zip(ops, program_of):
        key = (prog, instr(ev))
        lines.setdefault(key, ev.name)
        stack = op_name(ev.stats)
        if "/" in stack:    # (a parameter's copy carries its PATH: no stack)
            own[key] = stack
            node = node_of(stack)
            if node:
                by_node.setdefault(node, []).append((ev.start_ns, stack))

    def operands(key):
        """The instruction names `key`'s line holds: its `-start` first,
        and a `-done`'s `-start`'s own operands after its own."""
        line = lines[key].split(" = ", 1)[-1]
        done = _DONE_OF.search(line)
        names = ([done.group(1)] if done else []) + _NAMED.findall(line)
        if done and (key[0], done.group(1)) in lines:
            names += _NAMED.findall(
                lines[key[0], done.group(1)].split(" = ", 1)[-1])
        return names

    pending = [k for k in lines if k not in own]
    while pending:
        left = []
        for key in pending:
            stack = next((own[key[0], n] for n in operands(key)
                          if (key[0], n) in own), None)
            if stack is None:
                left.append(key)
            else:
                own[key] = stack
        if len(left) == len(pending):
            break
        pending = left
    # a node's PARAMETER among the operands: the stack of that node's next
    # operation in time, found an event at a time below
    for starts in by_node.values():
        starts.sort()
    keys = sorted(by_node, key=len, reverse=True)
    reads: Dict[Tuple, Optional[str]] = {}
    for key in pending:
        names = [n for n in operands(key) if "__" in n]
        reads[key] = next((k for k in keys
                           if any(f"__{k}__" in n for n in names)), None)
    out = []
    for ev, prog in zip(ops, program_of):
        key = (prog, instr(ev))
        stack = own.get(key, "")
        node = reads.get(key)
        if not stack and node:
            at = by_node[node]
            i = bisect.bisect_left(at, (ev.start_ns, ""))
            stack = at[min(i, len(at) - 1)][1]
        out.append(stack)
    return out


def build(ops, modules, scopes) -> Optional[Dict]:
    """The whole table from one chip's events (`xplane_stats.StatEvent`).
    Pure: tests feed it hand-built events. `scopes` is the program's
    `flexflow_tpu.obs.scopes`, or None in a checkout whose `scopes` cannot
    classify a serving stack: every operation of the step is then
    `unscoped`, and only what needs no scope is read."""
    if not ops or not modules:
        return None
    classify = scopes.classify_serving if scopes else lambda _s: (None,) * 3
    attn, named = (scopes.ATTN, scopes.GROUPS) if scopes else (None, ())
    by_program: Dict[str, float] = {}
    for ev in modules:
        base = _PROGRAM.sub("", ev.name)
        by_program[base] = by_program.get(base, 0.0) + ev.duration_ns
    step = max(by_program, key=by_program.get)
    launches = sum(1 for ev in modules if _PROGRAM.sub("", ev.name) == step)
    mods = sorted(modules, key=lambda ev: ev.start_ns)
    starts = [ev.start_ns for ev in mods]

    def program(ev) -> Optional[str]:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and ev.start_ns < mods[i].start_ns + mods[i].duration_ns:
            return mods[i].name
        return None

    program_of = [program(ev) for ev in ops]
    spans = [(ev.start_ns, ev.duration_ns) for ev in ops]
    own = self_times(spans)
    busy_ns = union_length([(s, s + d) for s, d in spans])
    cells: Dict[Tuple, float] = {}      # (group, part, kind) -> ns
    rows: Dict[Tuple, float] = {}       # + node kind and op family
    layout: Dict[Tuple, list] = {}      # -> [ns, instructions, an operand]
    by_stack: Dict[str, float] = {}     # the events' OWN stacks, seconds
    stale = partless = 0.0
    memo: Dict[str, Tuple] = {}

    def classified(stack: str) -> Tuple:
        if stack not in memo:
            memo[stack] = classify(stack)
        return memo[stack]

    def node_of(stack: str) -> Optional[str]:
        node = classified(stack)[1]
        return None if scopes is None or node == scopes.UNPACK else node

    for ev, prog, stack, mine in zip(ops, program_of,
                                     _stacks(ops, program_of, node_of), own):
        k = kind(ev.name)
        if prog is not None and _PROGRAM.sub("", prog) != step:
            group, node, part = OTHER_PROGRAMS, None, None
        else:
            group, node, part = classified(stack)
            if group is None:
                stale += mine if node is not None else 0.0
                group = UNSCOPED
            elif group == attn and part is None:
                partless += mine
        cells[group, part, k] = cells.get((group, part, k), 0.0) + mine
        row = (group, part, node_kind(node), family(ev.name))
        rows[row] = rows.get(row, 0.0) + mine
        written = op_name(ev.stats)
        by_stack[written] = by_stack.get(written, 0.0) + mine / 1e9
        if k == "layout":
            # the same operation of every layer is one row
            shapes, operand = describe(ev.name)
            what = (group, part, node_kind(node), family(ev.name), shapes)
            seen = layout.setdefault(what, [0.0, set(), operand])
            seen[0] += mine
            seen[1].add((prog, xplane.short_name(ev.name)))

    def ms(ns: float) -> float:
        return ns / 1e6 / launches

    groups: Dict[str, float] = {}
    parts: Dict[str, float] = {}
    for (group, part, _k), ns in cells.items():
        groups[group] = groups.get(group, 0.0) + ns
        if group == attn and part is not None:
            parts[part] = parts.get(part, 0.0) + ns
    scoped = any(g in groups for g in named)
    return {
        "step": step, "launches": launches, "programs": len(modules),
        "busy_ms": ms(busy_ns), "scoped": scoped,
        "cells": {k: ms(v) for k, v in cells.items()},
        "groups": {k: ms(v) for k, v in groups.items()},
        "parts": {k: ms(v) for k, v in parts.items()},
        "layout_ms": ms(sum(v for (_g, _p, k), v in cells.items()
                            if k == "layout")),
        "unscoped_share": 100.0 * groups.get(UNSCOPED, 0.0) / busy_ns,
        "stale_share": 100.0 * stale / busy_ns,
        "partless_ms": ms(partless),
        "rows": sorted(((ms(v), k) for k, v in rows.items()),
                       key=lambda r: -r[0]),
        "layout": sorted(((ms(ns), 100.0 * ns / busy_ns, key, len(instrs),
                           operand) for key, (ns, instrs, operand)
                          in layout.items()), key=lambda r: -r[0]),
        "by_stack": by_stack,
    }


def _log_table(t: Dict) -> None:
    log(f"serve_scope: step program {t['step']}: {t['launches']} launches "
        f"among {t['programs']} programs traced "
        f"({t['programs'] / t['launches']:.3f} a launch); busy "
        f"{t['busy_ms']:.3f} ms a launch")
    total = sum(t["groups"].values())
    log("serve_scope: ms a launch by group (self time): " + ", ".join(
        f"{g} {v:.3f}" for g, v in sorted(t["groups"].items(),
                                          key=lambda kv: -kv[1]))
        + f"; sum {total:.3f} = {100.0 * total / t['busy_ms']:.2f} % of "
        f"busy; unscoped {t['unscoped_share']:.2f} % of busy")
    if not t["scoped"]:
        log("serve_scope: NO operation of the step carries a group"
            + (f", and {t['stale_share']:.1f} % of busy names a graph "
               "node: an executable compiled BEFORE the groups (a stale "
               "compile cache?)" if t["stale_share"] else
               " (a program without them)"))
    elif t["stale_share"] > 0.5:
        log(f"serve_scope: WARNING {t['stale_share']:.1f} % of busy is in "
            "operations that name a graph node and NO group: some launch "
            "shape's executable predates the groups (a stale compile "
            "cache?)")
    if t["parts"]:
        log("serve_scope: the attention nodes by part: " + ", ".join(
            f"{p} {v:.3f}" for p, v in sorted(t["parts"].items(),
                                              key=lambda kv: -kv[1]))
            + f"; under no part {t['partless_ms']:.3f}")
    for (group, part, k), v in sorted(t["cells"].items(),
                                      key=lambda kv: -kv[1]):
        log(f"serve_scope:   {v:9.3f} ms  {group:14s} {part or '-':9s} {k}")
    for v, (group, part, node, fam) in t["rows"][:16]:
        log(f"serve_scope:   {v:9.3f} ms  {group:14s} {part or '-':9s} "
            f"{node:16s} {fam}")
    log(f"serve_scope: layout operations {t['layout_ms']:.3f} ms a launch "
        f"({100.0 * t['layout_ms'] / t['busy_ms']:.2f} % of busy); each of "
        "1 % of busy or more:")
    for v, share, (group, part, node, fam, shapes), n, operand in t["layout"]:
        if share >= 1.0:
            log(f"serve_scope:   {v:9.3f} ms {share:5.2f} %  {group}/"
                f"{part or '-'} {node}  {fam} x{n}  {shapes} {operand}")


def table(run) -> Optional[Dict]:
    """The run's table, parsed once; None where there is nothing to read."""
    if MEMO in run.extras:
        return run.extras[MEMO]
    run.extras[MEMO] = None
    try:
        from flexflow_tpu.obs import scopes
    except ImportError:
        scopes = None
    if not hasattr(scopes, "classify_serving"):
        scopes = None
    path = xplane.find_xplane(run.trace_dir()) if run.trace else None
    if path is None:
        return None
    planes = xplane_stats.read_device_planes(
        path, lines=(xplane.OPS_LINE, MODULES_LINE))
    if not planes:
        return None
    plane = planes[min(planes)]
    t = build(plane.lines.get(xplane.OPS_LINE, []),
              plane.lines.get(MODULES_LINE, []), scopes)
    if t is not None:
        # hand `scope_share` this parse: {name stack: self seconds}
        run.extras.setdefault(scope_share.MEMO, t["by_stack"])
        _log_table(t)
    run.extras[MEMO] = t
    return t


def read(run, what, group=None, part=None):
    t = table(run)
    if t is None:
        return None
    if what == "programs_per_launch":
        return t["programs"] / t["launches"]
    if what == "layout_ms":
        return t["layout_ms"]
    if not t["scoped"]:
        return None
    if what == "node_ms":
        return t["groups"].get(group, 0.0)
    if what == "attn_part_ms":
        return t["parts"].get(part, 0.0)
    if what == "unscoped_share":
        return t["unscoped_share"]
    raise ValueError(f"serve_scope: unknown `what` {what!r}")
