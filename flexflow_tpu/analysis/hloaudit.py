"""hloaudit — ground-truth static audit of lowered programs vs the
search cost model.

Search quality is bounded by cost-model fidelity (FlexFlow, MLSys'19),
and the consistency pass only cross-checks the DECLARED comm-spec for
attention — matmul TP all-reduces, DP grad sync, MoE all-to-alls, and
per-chip HBM were priced on trust. XLA gives a better oracle for free:
the whole step lowers to ONE optimized HLO module that can be parsed
statically (the full-compilation discipline of "Automatic Full
Compilation of Julia Programs to Cloud TPUs"). This pass AOT-lowers each
config's real jitted entry points (Executor.lowered_modules: train_step,
eval_step, ragged_step_fn at two shapes) on the multi-device CPU mesh,
parses the optimized HLO into a structured program summary —

  - the collective schedule: kind / replica groups / payload bytes per
    all-reduce, all-gather, all-to-all, collective-permute,
    reduce-scatter, attributed back to PCG nodes, to the phase of the
    step (forward / recompute / backward / optimizer) and to the mesh
    axes its replica groups span, through the jax.named_scopes the
    executor stamps into HLO metadata op_names: obs.scopes.classify and
    group_axes, the one reader of those names, which the benchmark's
    reader of a device trace shares;
  - transpose/copy overhead bytes (the round-4 backward-layout audit,
    folded in from tools/hlo_transpose_audit.py — one HLO parser in the
    tree);
  - peak per-chip HBM from XLA's buffer assignment (memory_analysis);

— and diffs it against what the search PRICED: the per-node manifest
CostModel.priced_comm_manifest exports (node_comm_events +
weight_sync_events + edge resharding, kind/axes/bytes per node). Findings:

  hlo-unpriced-collective (error)   the lowered program runs a collective
      at a node that priced nothing of that class — the search ranked
      strategies blind to it (the round-5 divergence class, now machine-
      caught).
  hlo-mispriced-bytes (warn/error)  priced vs lowered payload bytes for
      one (node, class) diverge beyond the tolerance band. Bands are wide
      by design: priced bytes are forward-pass global-tensor conventions
      while lowered payloads are per-shard with backward multiplicity.
  hlo-vanished-collective (info)    priced but absent from the artifact
      (XLA legally folds collectives; observability only).
  hlo-mem-divergence (warning)      priced memory_per_chip vs XLA's peak
      beyond the ratio band (above an absolute floor — tiny test configs
      are all constant overhead).
  hlo-hbm-budget (error)            a config whose priced or lowered
      per-chip peak exceeds the machine model's HBM — the memory-aware
      λ-search would steer INTO an OOM.
  hlo-transpose-overhead (info)     transpose+copy bytes above threshold
      (rank offenders with tools/hlo_transpose_audit.py).
  hlo-entry-failed (warning)        a train/eval entry point failed to
      lower or compile (decode entries skip as info).

The diff is deliberately class-coarse (reduce / gather / exchange):
GSPMD decomposes collectives (an expert all-to-all can lower as
all-gathers + collective-permutes; an all-reduce as reduce-scatter +
all-gather), and backward transposes them (the transpose of an
all-gather is a reduce-scatter). What must never happen is a class of
traffic the search priced at zero.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass
from flexflow_tpu.obs import scopes

# ---------------------------------------------------------------------------
# HLO text parsing (the one HLO parser in the tree; the transpose audit
# CLI wraps these same helpers)

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _literal_bytes(m: "re.Match") -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DT_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DT_BYTES[dt]


def shape_bytes(shape_str: str) -> int:
    """Bytes of every shape literal in an HLO type string summed (tuple
    types sum their members)."""
    return sum(_literal_bytes(m) for m in _SHAPE_RE.finditer(shape_str))


def _payload_bytes(type_str: str, is_start: bool) -> int:
    """Payload bytes of one collective's result type. Arrays and SYNC
    tuples (variadic combined collectives — every member is moved data)
    sum their literals. Async `-start` tuples vary across XLA versions:
    operand/result pairs (flat or nested, possibly variadic) double the
    moved bytes — detected as the member list being its own first half
    repeated, and halved — while array-plus-scratch layouts (e.g.
    `(f32[N], u32[], u32[])` collective-permute-start) are summed as-is,
    the scratch words being noise against the band tolerances."""
    members = [_literal_bytes(m) for m in _SHAPE_RE.finditer(type_str)]
    total = sum(members)
    if not (is_start and type_str.startswith("(")):
        return total
    n = len(members)
    if n >= 2 and n % 2 == 0 and members[:n // 2] == members[n // 2:]:
        return total // 2
    return total


# transpose/copy results are always array-typed; one pattern shared by
# audit_hlo_text (the CLI scan) and parse_hlo_module so they can't drift
_TRANSPOSE_RE = re.compile(r"%?[\w.\-]+ = (\S+) (transpose|copy)\(")


def audit_hlo_text(txt: str, min_bytes: int = 0) -> List[Dict]:
    """Scan optimized HLO text for transpose/copy instructions; returns
    [{kind, bytes, line}] largest first (fused bodies print the same
    instruction syntax, so fusions are covered line by line)."""
    out = []
    for line in txt.splitlines():
        s = line.strip()
        m = _TRANSPOSE_RE.match(s)
        if not m:
            continue
        nbytes = shape_bytes(m.group(1))
        if nbytes < min_bytes:
            continue
        out.append({"kind": m.group(2), "bytes": nbytes, "line": s[:220]})
    out.sort(key=lambda d: -d["bytes"])
    return out


# A LAYOUT operation only moves or re-lays data: one of these opcodes, or a
# fusion XLA named after nothing but these words (`bitcast_bitcast_fusion`;
# a TPU fusion is named after the opcodes it holds). The benchmark's reader
# of a serving trace decides the same way from an event's HLO line
# (benchmark/readers/serve_scope.py; tests hold the two rules equal).
LAYOUT_OPCODES = frozenset({
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "transpose", "reshape", "slice"})
LAYOUT_WORDS = frozenset({"bitcast", "copy", "transpose", "reshape",
                          "slice"})
_INSTR_RE = re.compile(
    r"^(?:ROOT )?%?([\w.\-]+) = (\((?:[^()]|\([^()]*\))*\)|\S+) "
    r"([a-z][\w\-]*)\((.*)$")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_NAMED_RE = re.compile(r"%([\w.\-]+)")


def is_layout(name: str, opcode: str) -> bool:
    """Whether the instruction `name` of `opcode` is a layout operation."""
    if opcode in LAYOUT_OPCODES:
        return True
    words = re.sub(r"\.\d+$", "", name).split("_")
    return (opcode == "fusion" and len(words) > 1 and words[-1] == "fusion"
            and all(w in LAYOUT_WORDS for w in words[:-1]))


def layout_operations(txt: str) -> List[Dict]:
    """The layout operations of one compiled SERVING module (the ragged
    step at a launch shape, compiled here for the CPU or for a described
    v5e), largest first: [{name, opcode, group, node, part, result,
    operand, bytes}]. What ROADMAP S10 asked to be named by hand from an
    HLO dump. `group`, `node` and `part` come from the instruction's
    `op_name` through obs.scopes.classify_serving; an instruction without
    one (a copy the compiler made) takes its first named operand's that
    has one, else, where an operand is a node's PARAMETER (a weight's
    prefetch), that node's next instruction's, as the trace reader does,
    so a copy is charged to the node it was made for. `result` and
    `operand` are shapes WITH their layouts;
    `bytes` the result's. Instructions inside a fusion's body are not
    operations of their own and are skipped; a `-done` repeats its
    `-start` and is skipped too."""
    fused = set(_CALLS_RE.findall(txt))
    own: Dict[str, str] = {}            # instruction -> its own name stack
    instrs: Dict[str, Tuple] = {}       # -> (line number, operand names)
    by_node: Dict[str, List[Tuple[int, str]]] = {}
    found = []
    done_at: Dict[str, int] = {}
    inside = None
    for at, line in enumerate(txt.splitlines()):
        s = line.strip()
        comp = _COMPUTATION_RE.match(s)
        if comp:
            inside = comp.group(1)
            continue
        m = _INSTR_RE.match(s)
        if not m or inside in fused:
            continue
        name, result, opcode, rest = m.groups()
        instrs[name] = (at, _NAMED_RE.findall(rest.split("), ")[0]))
        om = _OPNAME_RE.search(s)
        if om and "/" in om.group(1):
            # (a parameter's `op_name`, and its copy's, is its PATH in the
            # step's arguments, `trainable['l0_attn_5']['wq']`: no stack)
            own[name] = om.group(1)
            node = scopes.classify_serving(om.group(1))[1]
            if node and node != scopes.UNPACK:
                by_node.setdefault(node, []).append((at, om.group(1)))
        if opcode.endswith("-done"):
            # an asynchronous pair is USED where its `-done` stands
            done_at.update((o, at) for o in instrs[name][1][:1])
        elif is_layout(name, opcode):
            found.append((name, result, opcode, rest))
    keys = scopes.sorted_keys(by_node)

    def stack_of(name: str, depth: int = 0) -> str:
        if name in own or name not in instrs or depth > 8:
            return own.get(name, "")
        at, operands = instrs[name]
        stack = next(filter(None, (stack_of(o, depth + 1)
                                   for o in operands)), "")
        if not stack:
            # a node's PARAMETER (a weight's prefetch): the stack of that
            # node's next instruction in the module's schedule
            node = next((k for k in keys if any(f"__{k}__" in o
                                                for o in operands)), None)
            if node:
                used = done_at.get(name, at)
                later = [st for i, st in by_node[node] if i >= used]
                stack = later[0] if later else by_node[node][-1][1]
        own[name] = stack
        return stack

    out = []
    for name, result, opcode, rest in found:
        first = _NAMED_RE.search(rest)
        group, node, part = scopes.classify_serving(stack_of(name))
        out.append({
            "name": name, "opcode": opcode, "group": group, "node": node,
            "part": part, "result": result,
            "operand": (f"{rest[:first.start()]} %{first.group(1)}".strip()
                        if first else ""),
            "bytes": shape_bytes(result)})
    out.sort(key=lambda d: -d["bytes"])
    return out


_COLL_KINDS = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")

# the type is an array (`f32[...]`), a flat tuple (variadic combined
# collectives, async `-start` operand/result + scratch), or a one-level
# nested tuple (the combined variadic async form
# `((f32[...], ...), (f32[...], ...)) all-reduce-start`); `-done` lines
# never match, so each payload is counted once, at the start
_COLL_RE = re.compile(
    r"%?[\w.\-]+ = (\((?:[^()]|\([^()]*\))*\)|\S+) ("
    + "|".join(_COLL_KINDS) + r")(-start)?\("
)
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


_RNG_MARKERS = ("_uniform", "_bernoulli", "threefry", "random_bits",
                "random_gamma")


@dataclasses.dataclass
class LoweredCollective:
    """One collective instruction of the optimized module. `payload`
    follows the machine-model byte conventions the priced events use:
    per-chip operand for all-reduce / collective-permute, the full
    gathered (pre-scattered) tensor for all-gather (reduce-scatter),
    the per-chip tensor for all-to-all. `rng` marks partitioned-RNG
    plumbing (threefry counter exchanges under dropout): real wire
    traffic, but proportional to mask bits, attributed to whatever op
    holds the dropout — the cost model never prices it and the diff
    skips it (the bytes stay visible in the schedule stats). `node` and
    `phase` are obs.scopes.classify's reading of `op_name`; `axes` the
    mesh axes the replica groups span (obs.scopes.group_axes), empty
    when the parser was given no mesh."""

    kind: str
    payload: int
    group_size: int
    node: Optional[str]
    op_name: str
    line: str
    rng: bool = False
    phase: Optional[str] = None
    axes: Tuple[str, ...] = ()

    @property
    def comm_class(self) -> str:
        return _LOWERED_CLASS[self.kind]


@dataclasses.dataclass
class HLOSummary:
    """Structured summary of one entry point's optimized module."""

    collectives: List[LoweredCollective]
    transpose_bytes: int
    copy_bytes: int
    peak_bytes: Optional[int]  # per-chip, from buffer assignment

    def by_node(self) -> Dict[Optional[str], List[LoweredCollective]]:
        out: Dict[Optional[str], List[LoweredCollective]] = {}
        for c in self.collectives:
            out.setdefault(c.node, []).append(c)
        return out

    def schedule(self) -> Dict[str, Dict[str, float]]:
        """{kind: {count, payload_bytes, rng_bytes}} over the module."""
        out: Dict[str, Dict[str, float]] = {}
        for c in self.collectives:
            d = out.setdefault(c.kind, {"count": 0, "payload_bytes": 0,
                                        "rng_bytes": 0})
            d["count"] += 1
            d["payload_bytes"] += c.payload
            if c.rng:
                d["rng_bytes"] += c.payload
        return out

    def schedule_by_axes(self) -> Dict[str, Dict[str, float]]:
        """{"<axes>/<phase>": {count, payload_bytes}}: the same collectives
        by the mesh axes they cross and the phase of the step they belong
        to (`model/forward`, `data/backward`), which is how the
        benchmark's reader of a device trace names them."""
        out: Dict[str, Dict[str, float]] = {}
        for c in self.collectives:
            d = out.setdefault(f"{scopes.axes_label(c.axes)}/{c.phase}",
                               {"count": 0, "payload_bytes": 0})
            d["count"] += 1
            d["payload_bytes"] += c.payload
        return out


def peak_from_memory_stats(mem) -> Optional[int]:
    """Per-chip peak bytes from a CompiledMemoryStats (or the dict the
    CLI serializes it to): live arguments + outputs + XLA temp buffers,
    minus donated-alias double counting."""
    if mem is None:
        return None
    get = (mem.get if isinstance(mem, dict)
           else lambda k, d=0: getattr(mem, k, d))
    peak = (get("argument_size_in_bytes", 0) + get("output_size_in_bytes", 0)
            + get("temp_size_in_bytes", 0) - get("alias_size_in_bytes", 0))
    return int(peak) if peak > 0 else None


def collective_payload(line: str) -> Optional[Tuple[str, int, int]]:
    """(kind, payload bytes, replica-group size) of one HLO line when it
    is a collective instruction (sync, or the `-start` of an async pair;
    a `-done` is None, so a payload counts once), by LoweredCollective's
    byte conventions. The line may be an instruction of a module's text
    or the name of a device-trace event, which is the same line."""
    m = _COLL_RE.match(line)
    if not m:
        return None
    result_bytes = _payload_bytes(m.group(1), bool(m.group(3)))
    kind = m.group(2)
    groups = scopes.replica_groups(line)
    group_size = len(groups[0]) if groups else 1
    payload = result_bytes
    if kind == "reduce-scatter":
        payload = result_bytes * max(group_size, 1)
    return kind, payload, group_size


def parse_hlo_module(txt: str, node_keys: Sequence[str],
                     memory=None,
                     mesh_axes: Optional[Dict[str, int]] = None
                     ) -> HLOSummary:
    """Parse one optimized HLO module: every collective instruction
    (kind, replica-group size, payload bytes; PCG node and phase of the
    step through obs.scopes.classify from the named scopes in metadata
    op_name; with `mesh_axes`, {axis: size} in the mesh's order, the axes
    its groups span) plus transpose/copy overhead totals."""
    keys = scopes.sorted_keys(node_keys)
    colls: List[LoweredCollective] = []
    t_bytes = c_bytes = 0
    for line in txt.splitlines():
        s = line.strip()
        m = _TRANSPOSE_RE.match(s)
        if m:
            b = shape_bytes(m.group(1))
            if m.group(2) == "transpose":
                t_bytes += b
            else:
                c_bytes += b
            continue
        coll = collective_payload(s)
        if coll is None:
            continue
        kind, payload, group_size = coll
        om = _OPNAME_RE.search(s)
        op_name = om.group(1) if om else ""
        phase, node = scopes.classify(op_name, keys)
        rng = any(mk in op_name for mk in _RNG_MARKERS)
        axes = (scopes.group_axes(scopes.collective_groups(s), mesh_axes)
                if mesh_axes else ())
        colls.append(LoweredCollective(kind, payload, group_size, node,
                                       op_name, s[:240], rng=rng,
                                       phase=phase, axes=axes))
    return HLOSummary(colls, t_bytes, c_bytes,
                      peak_from_memory_stats(memory))


# ---------------------------------------------------------------------------
# diff: lowered artifact vs priced manifest

_LOWERED_CLASS = {"all-reduce": "reduce", "reduce-scatter": "reduce",
                  "all-gather": "gather", "all-to-all": "exchange",
                  "collective-permute": "exchange"}
_PRICED_CLASS = {"all_reduce": "reduce", "psum": "reduce",
                 "reduce_scatter": "reduce", "all_gather": "gather",
                 "all_to_all": "exchange", "ppermute": "exchange"}
# priced classes that can legitimately produce each lowered OPCODE:
# GSPMD decomposes an all-to-all into all-gathers/permutes, reassociates
# an all-reduce into reduce-scatter + all-gather, and the BACKWARD of an
# all-gather is a reduce-scatter (so priced gather traffic shows up as
# reduce-scatters in a training module) — but a lowered all-REDUCE can
# only come from priced reduce traffic, which is what makes zeroing a
# priced psum detectable
_SATISFIED_BY = {
    "all-reduce": ("reduce",),
    "reduce-scatter": ("reduce", "gather"),
    "all-gather": ("gather", "exchange", "reduce"),
    "all-to-all": ("exchange",),
    "collective-permute": ("exchange",),
}


@dataclasses.dataclass
class AuditOptions:
    """Tolerances. Byte bands are wide BY DESIGN: priced bytes follow the
    machine-formula conventions (global forward-pass tensors) while
    lowered payloads are per-shard with backward multiplicity — the audit
    exists to catch class-level blindness and order-of-magnitude drift,
    not to re-derive GSPMD."""

    # lowered collectives below this payload never error (latency-bound
    # chatter: loss/metric scalars, index plumbing)
    unpriced_floor_bytes: float = 64e3
    # byte-ratio checks apply only above this payload
    ratio_floor_bytes: float = 1e6
    ratio_warn: float = 8.0
    ratio_error: float = 64.0
    # memory divergence checks apply only above this size
    mem_floor_bytes: float = 64e6
    mem_ratio_warn: float = 8.0
    transpose_info_bytes: float = 256e6


def _fmt_mb(b: float) -> str:
    if b >= 1e9:
        return f"{b / 1e9:.2f}GB"
    if b >= 1e6:
        return f"{b / 1e6:.1f}MB"
    return f"{b / 1e3:.0f}KB"


def _event_fields(ev) -> Tuple[str, Tuple[str, ...], float, str]:
    """(kind, axes, nbytes, source) from a PricedEvent or a plain dict
    (tests build manifests by hand; the CLI may round-trip JSON)."""
    if isinstance(ev, dict):
        return (ev["kind"], tuple(ev.get("axes", ())),
                float(ev["nbytes"]), ev.get("source", "node_comm"))
    return ev.kind, tuple(ev.axes), float(ev.nbytes), ev.source


def diff_entry(subject: str, entry: str, manifest: Optional[Dict],
               summary: HLOSummary, opts: Optional[AuditOptions] = None,
               ) -> List[Finding]:
    """Diff one entry point's lowered collective schedule against the
    priced manifest. `manifest` is CostModel.priced_comm_manifest output
    (None for unpriced entry points — decode paths get schedule/memory
    observability but no comm diff)."""
    opts = opts or AuditOptions()
    findings: List[Finding] = []
    if manifest is None:
        return findings

    # priced classes (and bytes) per node: node events + incident edges
    priced_by_node: Dict[str, Dict[str, float]] = {}
    priced_kinds: Dict[str, set] = {}
    for key, evs in manifest.get("nodes", {}).items():
        for ev in evs:
            kind, _axes, nbytes, _src = _event_fields(ev)
            cls = _PRICED_CLASS[kind]
            d = priced_by_node.setdefault(key, {})
            d[cls] = d.get(cls, 0.0) + nbytes
            priced_kinds.setdefault(key, set()).add(kind)
    edge_classes: Dict[str, set] = {}
    for e in manifest.get("edges", ()):
        cls = _PRICED_CLASS[e["kind"]]
        for end in (e["src"], e["dst"]):
            edge_classes.setdefault(end, set()).add(cls)

    lowered_by_node: Dict[str, Dict[str, float]] = {}
    for c in summary.collectives:
        if c.node is None or c.rng:
            # loss/metrics/optimizer plumbing outside node scopes, and
            # partitioned-RNG counter exchanges the model never prices
            continue
        d = lowered_by_node.setdefault(c.node, {})
        d[c.kind] = d.get(c.kind, 0.0) + c.payload

    where = lambda key: f"{subject}:{entry}:{key}" if subject \
        else f"{entry}:{key}"  # noqa: E731

    for key, kinds in sorted(lowered_by_node.items()):
        have = set(priced_by_node.get(key, ()))
        have_edges = edge_classes.get(key, set())
        for kind, payload in sorted(kinds.items()):
            ok = set(_SATISFIED_BY[kind])
            if ok & have or ok & have_edges:
                # priced — check magnitude (node-priced bytes of every
                # class that can produce this opcode)
                priced_bytes = sum(priced_by_node.get(key, {}).get(c, 0.0)
                                   for c in ok)
                if (payload >= opts.ratio_floor_bytes
                        and priced_bytes > 0.0):
                    ratio = payload / priced_bytes
                    band = max(ratio, 1.0 / ratio)
                    if band > opts.ratio_warn:
                        sev = ("error" if band > opts.ratio_error
                               else "warning")
                        findings.append(Finding(
                            "hloaudit", sev, "hlo-mispriced-bytes",
                            where(key),
                            f"{kind} traffic diverges {band:.1f}x beyond "
                            f"the priced manifest: the lowered module "
                            f"moves {_fmt_mb(payload)} but the cost "
                            f"model priced {_fmt_mb(priced_bytes)} "
                            f"({sorted(priced_kinds.get(key, ()))}) — "
                            "the search ranks this node's strategies on "
                            "bytes the machine does not move"))
                continue
            if payload < opts.unpriced_floor_bytes:
                continue
            findings.append(Finding(
                "hloaudit", "error", "hlo-unpriced-collective",
                where(key),
                f"lowered HLO runs {kind} ({_fmt_mb(payload)} payload) "
                f"at this node, but the cost model priced no "
                f"{'/'.join(ok)}-class collective there (priced kinds: "
                f"{sorted(priced_kinds.get(key, ())) or '(none)'}) — "
                "the search is blind to this traffic (the round-5 "
                "divergence class); align CostModel pricing with the "
                "lowering or fix the strategy view"))

    # priced-but-vanished: observability (XLA legally folds collectives)
    for key, classes in sorted(priced_by_node.items()):
        lowered = lowered_by_node.get(key, {})
        for cls, nbytes in sorted(classes.items()):
            produced = {lc for lc, srcs in _SATISFIED_BY.items()
                        if cls in srcs}
            if nbytes >= opts.ratio_floor_bytes and not (
                    produced & set(lowered)):
                findings.append(Finding(
                    "hloaudit", "info", "hlo-vanished-collective",
                    where(key),
                    f"cost model prices {_fmt_mb(nbytes)} of {cls}-class "
                    f"comm here but the lowered module runs none — "
                    "either XLA folded it or the strategy overprices"))
    return findings


def check_memory(subject: str, entry: str, priced_mem: float,
                 summary: Optional[HLOSummary], machine,
                 opts: Optional[AuditOptions] = None) -> List[Finding]:
    """HBM checks for one entry: the budget gate (error — the
    memory-aware λ-search must not steer on numbers that OOM) and the
    priced-vs-buffer-assignment ratio band (warning, above the floor)."""
    opts = opts or AuditOptions()
    findings: List[Finding] = []
    where = f"{subject}:{entry}" if subject else entry
    budget = machine.memory_per_chip()
    peak = summary.peak_bytes if summary is not None else None
    if priced_mem > budget:
        findings.append(Finding(
            "hloaudit", "error", "hlo-hbm-budget", where,
            f"priced memory_per_chip {_fmt_mb(priced_mem)} exceeds the "
            f"machine model's HBM budget {_fmt_mb(budget)} "
            f"({machine.chip.name}) — the memory-aware search would "
            "select a strategy that cannot fit"))
    if peak is not None and peak > budget:
        findings.append(Finding(
            "hloaudit", "error", "hlo-hbm-budget", where,
            f"XLA buffer assignment peaks at {_fmt_mb(peak)} per chip, "
            f"over the {_fmt_mb(budget)} HBM budget "
            f"({machine.chip.name}) — this program OOMs on the modeled "
            "machine regardless of what the search priced"))
    if (peak is not None and priced_mem > 0
            and max(peak, priced_mem) >= opts.mem_floor_bytes):
        ratio = peak / priced_mem
        band = max(ratio, 1.0 / ratio)
        if band > opts.mem_ratio_warn:
            findings.append(Finding(
                "hloaudit", "warning", "hlo-mem-divergence", where,
                f"XLA peak {_fmt_mb(peak)} vs priced "
                f"{_fmt_mb(priced_mem)} per chip diverge {band:.1f}x — "
                "the memory-aware λ-search is steering on unvalidated "
                "numbers; recalibrate CostModel.node_memory"))
    return findings


def check_transposes(subject: str, entry: str, summary: HLOSummary,
                     opts: Optional[AuditOptions] = None) -> List[Finding]:
    opts = opts or AuditOptions()
    total = summary.transpose_bytes + summary.copy_bytes
    if total < opts.transpose_info_bytes:
        return []
    where = f"{subject}:{entry}" if subject else entry
    return [Finding(
        "hloaudit", "info", "hlo-transpose-overhead", where,
        f"optimized module carries {_fmt_mb(summary.transpose_bytes)} of "
        f"transposes + {_fmt_mb(summary.copy_bytes)} of copies — rank "
        "offenders with tools/hlo_transpose_audit.py and fix the "
        "lowering's layout (VERDICT r4 #2 discipline)")]


# ---------------------------------------------------------------------------
# the registered pass

PRICED_ENTRIES = ("train_step", "eval_step")


@register_pass("hloaudit")
def hloaudit_pass(ctx: AnalysisContext) -> List[Finding]:
    """Diff ctx.hlo_modules ({entry: {"hlo_text", "memory", optionally
    "error"}}) against ctx.cost_model's priced manifest for ctx.graph.
    The CLI fills hlo_modules via Executor.lowered_modules() +
    .compile(); tests inject text directly. Skips silently when the
    lowering inputs are absent (pass-registry contract)."""
    if ctx.graph is None or ctx.hlo_modules is None \
            or ctx.cost_model is None:
        return []
    from flexflow_tpu.search.cost_model import graph_cost

    opts = ctx.hlo_opts if isinstance(ctx.hlo_opts, AuditOptions) else (
        AuditOptions(**(ctx.hlo_opts or {})))
    node_keys = [n.stable_key() for n in ctx.graph.nodes]
    strategy = dict(ctx.strategy or {})
    findings: List[Finding] = []
    summary_out: Dict[str, Dict] = {}
    for entry, mod in sorted(ctx.hlo_modules.items()):
        where = f"{ctx.subject}:{entry}" if ctx.subject else entry
        if mod.get("error"):
            sev = "warning" if entry in PRICED_ENTRIES else "info"
            findings.append(Finding(
                "hloaudit", sev, "hlo-entry-failed", where,
                f"entry point failed to lower/compile: {mod['error']}"))
            continue
        summary = parse_hlo_module(mod["hlo_text"], node_keys,
                                   memory=mod.get("memory"),
                                   mesh_axes=ctx.axis_sizes)
        training = entry == "train_step"
        priced = entry in PRICED_ENTRIES
        manifest = None
        if priced:
            manifest = ctx.cost_model.priced_comm_manifest(
                ctx.graph, strategy or None, training=training)
            findings += diff_entry(ctx.subject, entry, manifest, summary,
                                   opts)
            gc = graph_cost(ctx.graph, strategy, ctx.cost_model,
                            training=training)
            findings += check_memory(ctx.subject, entry, gc.memory_per_chip,
                                     summary, ctx.cost_model.machine, opts)
        elif summary.peak_bytes is not None:
            findings += check_memory(ctx.subject, entry, 0.0, summary,
                                     ctx.cost_model.machine, opts)
        findings += check_transposes(ctx.subject, entry, summary, opts)
        summary_out[entry] = {
            "collective_schedule": summary.schedule(),
            "collectives_by_axis": summary.schedule_by_axes(),
            "attributed": sum(1 for c in summary.collectives
                              if c.node is not None),
            "unattributed": sum(1 for c in summary.collectives
                                if c.node is None),
            "transpose_bytes": summary.transpose_bytes,
            "copy_bytes": summary.copy_bytes,
            "peak_bytes": summary.peak_bytes,
            "priced": priced,
        }
    if ctx.hlo_summary is None:
        ctx.hlo_summary = {}
    ctx.hlo_summary[ctx.subject or "module"] = summary_out
    return findings


# ---------------------------------------------------------------------------
# driver: lower + compile one executor's entry points into ctx.hlo_modules

def lower_executor_modules(executor,
                           entries: Optional[Sequence[str]] = None,
                           hlo_dump: Optional[str] = None,
                           subject: str = "") -> Dict[str, Dict]:
    """AOT-lower + XLA-compile an Executor's entry points into the
    {entry: {"hlo_text", "memory"} | {"error"}} mapping hloaudit_pass
    consumes. Nothing executes — only compiles. With `hlo_dump`, each
    optimized module is also written to <hlo_dump>/<subject>_<entry>.txt
    for offline diffing."""
    import os

    out: Dict[str, Dict] = {}
    if entries is None:
        entries = ["train_step", "eval_step"]
        if executor.can_paged_decode():
            entries += ["paged_decode", "verify"]
    for entry in entries:
        # one entry per lowered_modules() call: a decode path that cannot
        # trace must not take the train/eval audit down with it
        try:
            low = executor.lowered_modules([entry])[entry]
        except Exception as e:
            out[entry] = {"error": f"{type(e).__name__}: {e}"}
            continue
        try:
            compiled = low.compile()
            txt = compiled.as_text()
            try:
                mem = compiled.memory_analysis()
            except Exception:
                mem = None
            out[entry] = {"hlo_text": txt, "memory": mem}
            if hlo_dump:
                os.makedirs(hlo_dump, exist_ok=True)
                name = f"{subject}_{entry}.txt" if subject else f"{entry}.txt"
                with open(os.path.join(hlo_dump, name), "w") as f:
                    f.write(txt)
        except Exception as e:
            out[entry] = {"error": f"{type(e).__name__}: {e}"}
    return out
