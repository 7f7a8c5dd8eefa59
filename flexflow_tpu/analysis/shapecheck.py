"""shapecheck — static launch-shape-space auditor.

Every distinct input shape hitting one of Executor's `jax.jit` entry
points is a fresh XLA compilation. The serving hot paths are built so
that the set of reachable launch shapes per served config is CLOSED and
small (ragged windows capped at PREFILL_WINDOW_ROWS, pow2 prefill
buckets, fixed spec-tree node counts) — a shape-polymorphic regression
turns that into a compile storm that blows TTFT SLOs in production. This
pass proves the closure holds, three ways:

  1. AST/dataflow arm: walks the launch sites in `paged/scheduler.py`,
     `spec/server.py`, `serving.py`, and `runtime/executor.py`, and
     classifies every symbolic width feeding a launch as *clamped*
     (derived through an explicit bound — `min(..., CAP)`, a pow2
     `_bucket`, or a config constant/attribute) or *unbounded* (derived
     from request-sized data like `len(prompt)` with no clamp).

  shape-space-unbounded (error)   a launch width taints back to
      request-sized data with no clamp on the path — every new request
      length compiles a fresh XLA program. The finding names the taint
      chain line by line.
  shape-space-over-budget (warning) a served config's enumerated
      shape space exceeds the compile budget (`--shape-budget`,
      default DEFAULT_SHAPE_BUDGET) — legal, but warmup pays one
      compile per shape, so the catalog size is an SLO input.
  shape-catalog-unsound (error)   a runtime compile event landed on a
      shape absent from the static catalog (check_soundness — the CI
      gate that keeps the enumeration honest).
  stale-pragma (info)             a '# fflint: shape-ok' pragma that no
      longer suppresses anything.

  2. Enumeration arm: `enumerate_catalog(...)` computes, per served
     config, the closed set of reachable launch shapes per jit entry
     point and the upper bound on distinct compilations — the
     machine-readable catalog lands in `stats.shapecheck` and drives
     `Executor.warm_launch_shapes` (obs/compile_tracker.py is the
     matching runtime arm).

  3. Soundness arm: `check_soundness(catalog, events)` diffs observed
     compile events (CompileTracker.observed()) against the catalog —
     steady-state serving after warmup must observe ZERO events, and
     every warmup event must be enumerated.

Suppression: a flagged launch line (or its enclosing loop header)
carrying `# fflint: shape-ok` / `# fflint: ignore` is skipped.
`jit_entry_points(path)` reports the jit call sites the pass saw, so a
gate test can assert the rule engaged (a clean scan proves nothing if
no entry point was seen).
"""

from __future__ import annotations

import ast
import io
import os
import tokenize
from typing import Dict, List, Optional, Sequence, Set, Tuple

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass

# The scheduler's packed-prefill window cap (serve_strategy.py
# PREFILL_WINDOW_ROWS). Mirrored as a plain int so the pass never
# imports the serving stack (fflint must run on a bare checkout);
# tests/test_analysis.py asserts the two constants agree.
PREFILL_WINDOW_ROWS = 8

# Default upper bound on distinct compilations per served config before
# shape-space-over-budget fires (override via --shape-budget /
# AnalysisContext.shapecheck_budget).
DEFAULT_SHAPE_BUDGET = 64

# The four launch-shape-bearing hot-path files the AST arm audits,
# relative to the flexflow_tpu package root.
DEFAULT_SUBJECTS = ("paged/scheduler.py", "spec/server.py", "serving.py",
                    "runtime/executor.py")

# Methods whose call sites ARE ragged launches: positional index of the
# symbolic width argument (after self).
_LAUNCH_WIDTH_ARG = {"_launch": 1}

# Calls that CLAMP their argument into a closed family regardless of
# taint: the pow2 bucket maps any take into {8, 16, ..., bucket(cap)}.
_BUCKET_CALLS = {"_bucket", "bucket"}

# Calls whose result is request-sized data — the taint sources.
_UNBOUNDED_CALLS = {"len"}


def default_src_paths() -> List[str]:
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(base, p) for p in DEFAULT_SUBJECTS]


# ---------------------------------------------------------------------------
# AST/dataflow arm


def _dotted(node: ast.AST) -> Optional[tuple]:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _short(node: ast.AST, limit: int = 48) -> str:
    try:
        txt = ast.unparse(node)
    except Exception:
        txt = type(node).__name__
    return txt if len(txt) <= limit else txt[:limit - 3] + "..."


def _is_directive(txt: str) -> bool:
    if "fflint:" not in txt:
        return False
    directive = txt.split("fflint:", 1)[1].strip()
    return directive.startswith("shape-ok") or directive.startswith("ignore")


def _is_own_directive(txt: str) -> bool:
    """Only shape-ok pragmas are OURS to flag stale — a shared
    '# fflint: ignore' may be earning its keep for another pass."""
    if "fflint:" not in txt:
        return False
    return txt.split("fflint:", 1)[1].strip().startswith("shape-ok")


def _comment_map(src: str) -> Dict[int, str]:
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError):
        pass  # ast.parse already succeeded; a tokenizer hiccup only
        # costs pragma visibility, never findings
    return out


def _suppressed(comments: Dict[int, str], *linenos: int) -> Optional[int]:
    for ln in linenos:
        if _is_directive(comments.get(ln, "")):
            return ln
    return None


# taint = (unbounded: bool, chain: [(lineno, description), ...]).
_CLAMPED = (False, [])


class _TaintScanner(ast.NodeVisitor):
    """Intra-function dataflow over the symbolic widths feeding launch
    sites. Deliberately OPTIMISTIC at unknowns (params, attributes,
    unrecognized calls default to clamped): the error is reserved for a
    width that DEFINITELY taints back to request-sized data — same
    direct-body, low-noise contract as the hostsync pass."""

    def __init__(self, findings, rel, comments, fn_name,
                 used_pragmas: Set[int]):
        self.findings = findings
        self.rel = rel
        self.comments = comments
        self.fn_name = fn_name
        self.loop_stack: List[int] = []
        self.used_pragmas = used_pragmas
        self.state: Dict[str, tuple] = {}

    # -- classification ---------------------------------------------------

    def _classify(self, node: ast.AST) -> tuple:
        if isinstance(node, ast.Constant):
            return _CLAMPED
        if isinstance(node, ast.Name):
            return self.state.get(node.id, _CLAMPED)
        if isinstance(node, ast.Attribute):
            # self.prefill_chunk / self.spec.max_nodes / module constants:
            # config-derived, bounded by construction
            return _CLAMPED
        if isinstance(node, ast.Call):
            return self._classify_call(node)
        if isinstance(node, ast.BinOp):
            lu, lc = self._classify(node.left)
            ru, rc = self._classify(node.right)
            return (lu or ru, lc + rc)
        if isinstance(node, ast.UnaryOp):
            return self._classify(node.operand)
        if isinstance(node, ast.IfExp):
            bu, bc = self._classify(node.body)
            ou, oc = self._classify(node.orelse)
            return (bu or ou, bc + oc)
        return _CLAMPED

    def _classify_call(self, node: ast.Call) -> tuple:
        d = _dotted(node.func)
        fname = d[-1] if d else None
        if fname in _UNBOUNDED_CALLS:
            return (True, [(node.lineno, _short(node))])
        if fname in _BUCKET_CALLS:
            # pow2 bucketing maps any input into a closed family — an
            # explicit bound in the ISSUE's sense. (An uncapped bucket of
            # a raw length is still one compile per pow2 class; the
            # enumeration arm prices that family, it is not a storm.)
            return _CLAMPED
        if fname == "min":
            results = [self._classify(a) for a in node.args]
            if any(not u for u, _ in results):
                return _CLAMPED  # one clamped operand bounds the min
            chain = [c for u, ch in results if u for c in ch]
            return (bool(chain), chain)
        if fname in ("max", "sum"):
            # max/sum are unbounded as soon as ONE operand is
            results = [self._classify(a) for a in node.args]
            chain = [c for u, ch in results if u for c in ch]
            return (bool(chain), chain)
        return _CLAMPED

    # -- statement walking ------------------------------------------------

    def _assign_name(self, name: str, value: ast.AST, lineno: int):
        u, chain = self._classify(value)
        if u and (not chain or chain[-1][0] != lineno):
            chain = chain + [(lineno, f"{name} = {_short(value)}")]
        self.state[name] = (u, chain)

    def visit_Assign(self, node):
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                self._assign_name(tgt.id, node.value, node.lineno)
            elif isinstance(tgt, ast.Tuple) and isinstance(node.value,
                                                           ast.Tuple) \
                    and len(tgt.elts) == len(node.value.elts):
                for t, v in zip(tgt.elts, node.value.elts):
                    if isinstance(t, ast.Name):
                        self._assign_name(t.id, v, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if isinstance(node.target, ast.Name):
            prev = self.state.get(node.target.id, _CLAMPED)
            u, chain = self._classify(node.value)
            self.state[node.target.id] = (prev[0] or u, prev[1] + chain)
        self.generic_visit(node)

    # nested defs are separate scopes (same contract as hostsync)
    def visit_FunctionDef(self, node):
        return

    visit_AsyncFunctionDef = visit_FunctionDef

    def _loop(self, node):
        self.loop_stack.append(node.lineno)
        self.generic_visit(node)
        self.loop_stack.pop()

    visit_For = visit_While = _loop

    def _add(self, severity, code, lineno, msg):
        used = _suppressed(self.comments, lineno, *self.loop_stack)
        if used is not None:
            self.used_pragmas.add(used)
            return
        self.findings.append(Finding(
            "shapecheck", severity, code, f"{self.rel}:{lineno}",
            f"in {self.fn_name}(): {msg}"))

    def visit_Call(self, node):
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _LAUNCH_WIDTH_ARG:
            idx = _LAUNCH_WIDTH_ARG[node.func.attr]
            width = None
            if len(node.args) > idx:
                width = node.args[idx]
            else:
                for kw in node.keywords:
                    if kw.arg == "window":
                        width = kw.value
            if width is not None:
                u, chain = self._classify(width)
                if u:
                    steps = chain + [(node.lineno,
                                      f"launch width {_short(width)}")]
                    trace = " -> ".join(
                        f"line {ln}: {d}" for ln, d in steps)
                    self._add(
                        "error", "shape-space-unbounded", node.lineno,
                        f"launch width {_short(width)!r} derives from "
                        "request-sized data with no clamp — every new "
                        "value compiles a fresh XLA program (a compile "
                        "storm under real traffic); bound it with "
                        "min(..., CAP), a pow2 bucket, or a config "
                        f"constant. taint: {trace}")
        self.generic_visit(node)


def jit_entry_points(path: str) -> List[Dict]:
    """Every `jax.jit(...)` call site in `path`, with the enclosing
    function scope ({scope, line} per site). A gate test pairs this with
    scan_file: a clean scan only proves closure when the entry points
    were actually seen."""
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    out: List[Dict] = []

    def walk(node: ast.AST, scope: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk(child, child.name)
                continue
            if isinstance(child, ast.Call):
                d = _dotted(child.func)
                if d and d[-1] == "jit":
                    out.append({"scope": scope, "line": child.lineno})
            walk(child, scope)

    walk(tree, "<module>")
    return out


def scan_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    rel = rel or os.path.basename(path)
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("shapecheck", "error", "syntax-error",
                        f"{rel}:{e.lineno}", str(e))]
    comments = _comment_map(src)
    findings: List[Finding] = []
    used_pragmas: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scanner = _TaintScanner(findings, rel, comments, node.name,
                                    used_pragmas)
            for child in node.body:
                scanner.visit(child)
    for ln, txt in sorted(comments.items()):
        if _is_own_directive(txt) and ln not in used_pragmas:
            findings.append(Finding(
                "shapecheck", "info", "stale-pragma", f"{rel}:{ln}",
                "'# fflint: shape-ok' pragma no longer suppresses any "
                "finding — delete it (stale annotations rot into blanket "
                "noise)"))
    findings.sort(key=lambda f: f.where)
    return findings


def scan_paths(paths: Sequence[str]) -> List[Finding]:
    findings: List[Finding] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, _dirs, files in os.walk(p):
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        full = os.path.join(dirpath, fn)
                        rel = os.path.relpath(
                            full, os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))))
                        findings += scan_file(full, rel)
        elif os.path.exists(p):
            findings += scan_file(p, os.path.basename(p))
    return findings


# ---------------------------------------------------------------------------
# Enumeration arm — the closed launch-shape catalog per served config


def _pow2_buckets(n: int) -> List[int]:
    """Reachable `_bucket(take)` values for take in 1..n: {8, ..., bucket(n)}."""
    vals = []
    b = 8
    while b < n:
        vals.append(b)
        b *= 2
    vals.append(b)
    return vals


def _dense_prefill_lens(max_len: int) -> List[int]:
    """Dense admission pads to min(_bucket(len(seq)), max_len)."""
    vals = {b for b in _pow2_buckets(max_len) if b < max_len}
    vals.add(max_len)
    return sorted(vals)


def _packed_prefill_shapes(slots: int, chunk: int,
                           cap: int = PREFILL_WINDOW_ROWS,
                           item_bucket: int = 1) -> Set[Tuple[int, int]]:
    """Closed (n_items, window) family of the scheduler's ragged-packed
    prefill tick: W = min(cap, largest take this tick); each planned
    slot's take splits into ceil(take/W) pieces, all packed into ONE
    launch with one q_len 1 item a DECODING slot behind them; the shared
    per-tick token budget bounds sum(take) by prefill_chunk, and k
    mid-prefill plus d decoding slots are at most `slots`. For W < cap,
    W IS the largest take, so every take fits one piece and n_items <=
    k + d <= slots (one chunk and slots-1 decode rows reach it). At
    W == cap takes may exceed the window and split, so n_items is
    bounded by the worst split: one take of the whole budget and a
    decode row on every other slot (a single-row take in a decode row's
    place costs the big take a row and never adds a piece).

    With `item_bucket` > 1 (a graph with state layers, whose programs are
    the dearest to compile) the scheduler always launches the full window
    and fills the launch with items without rows up to a multiple of
    `item_bucket`: one shape a multiple up to the worst split."""
    if item_bucket > 1:
        W = min(cap, chunk)
        bmax = slots - 1 + -(-chunk // W)
        return {(B, W) for B in range(item_bucket, bmax + item_bucket,
                                      item_bucket)}
    shapes: Set[Tuple[int, int]] = set()
    for W in range(1, min(cap, chunk) + 1):
        bmax = slots if W < cap else slots - 1 + -(-chunk // W)
        for B in range(1, bmax + 1):
            shapes.add((B, W))
    return shapes


def enumerate_catalog(*, slots: int, max_len: int, paged: bool = True,
                      page_size: int = 64,
                      prefill_chunk: int = 64,
                      spec_max_nodes: Optional[int] = None,
                      spec_depth: Optional[int] = None,
                      num_pages: Optional[int] = None,
                      kv_dtype: str = "auto",
                      window_rows: int = PREFILL_WINDOW_ROWS,
                      num_pages_window: Optional[int] = None,
                      item_bucket: int = 1) -> Dict:
    """The closed set of reachable launch shapes per jit entry point for
    ONE served config, plus the config echo `Executor.warm_launch_shapes`
    needs to rebuild the launch arguments (table width, pool size,
    dtype). Shapes are the CompileTracker's canonical signatures — the
    ids/window dims of each entry's symbolic argument — so observed
    compile events diff directly against the catalog
    (check_soundness)."""
    slots = int(slots)
    max_len = int(max_len)
    entries: Dict[str, Dict] = {}

    def entry(name: str, shapes) -> None:
        uniq = sorted({tuple(int(x) for x in s) for s in shapes})
        entries[name] = {"shapes": [list(s) for s in uniq],
                         "count": len(uniq)}

    if paged:
        ragged: Set[Tuple[int, int]] = {(slots, 1)}  # decode tick
        ragged |= _packed_prefill_shapes(slots, int(prefill_chunk),
                                         int(window_rows), int(item_bucket))
        if spec_max_nodes:
            T = int(spec_max_nodes)
            # verify packs only drafting + sampled-root slots —
            # idle/mid-prefill slots pack nothing
            ragged |= {(b, T) for b in range(1, slots + 1)}
        entry("ragged_step", ragged)
        if spec_max_nodes:
            depth = int(spec_depth) if spec_depth else 1
            entry("paged_commit", [(slots, depth + 1)])
    else:
        dense = {(slots, 1)}
        dense |= {(1, L) for L in _dense_prefill_lens(max_len)}
        entry("decode_step", dense)
    # the shared sampling program sees (slots, V) decode rows and (1, V)
    # first-token rows; V is a model property, so the catalog keys the
    # batch dim only
    entry("pick_tokens", [(slots,), (1,)])

    slack = int(spec_max_nodes) if spec_max_nodes else 0
    table_cols = -(-(max_len + slack) // int(page_size)) if paged else 0
    if paged and num_pages is None:
        num_pages = slots * table_cols + 1
    return {
        "version": 1,
        "config": {
            "slots": slots, "max_len": max_len, "paged": bool(paged),
            "page_size": int(page_size) if paged else None,
            "prefill_chunk": int(prefill_chunk) if paged else None,
            "spec_max_nodes": int(spec_max_nodes) if spec_max_nodes else None,
            "spec_depth": int(spec_depth) if spec_depth else None,
            "num_pages": int(num_pages) if num_pages else None,
            "table_cols": table_cols,
            "kv_dtype": str(kv_dtype),
            "window_rows": int(window_rows),
            # the window class's pages, where the graph has window layers
            **({"num_pages_window": int(num_pages_window)}
               if num_pages_window else {}),
            # a state graph's launches, filled to a multiple of this
            **({"item_bucket": int(item_bucket)}
               if item_bucket > 1 else {}),
        },
        "entries": entries,
        "total_compilations": sum(e["count"] for e in entries.values()),
    }


def catalog_for_strategy(strategy, *, slots: int, max_len: int) -> Dict:
    """enumerate_catalog for a serve_strategy.ServeStrategy — the
    `tools/servesearch.py explain` compile_cost line prices this."""
    sp = strategy.spec_config()
    kw = strategy.to_server_kwargs(slots=slots, max_len=max_len)
    return enumerate_catalog(
        slots=slots, max_len=max_len, paged=True,
        page_size=kw["page_size"], prefill_chunk=kw["prefill_chunk"],
        spec_max_nodes=sp.max_nodes if sp else None,
        spec_depth=sp.depth if sp else None,
        num_pages=kw["num_pages"], kv_dtype=kw["kv_dtype"])


def union_catalogs(*catalogs: Dict) -> Dict:
    """Merge launch-shape catalogs into one whose entries enumerate the
    UNION of every input's shapes — the catalog a drain-and-swap
    cutover is judged against (serving_autopilot): while requests from
    both sides are in flight, a compile event is sound if EITHER
    strategy's enumeration reaches it. Configs are kept as a list for
    provenance; total_compilations is recomputed over the union (shapes
    shared by both sides count once — warmed once, reused across the
    swap)."""
    if not catalogs:
        raise ValueError("union_catalogs needs at least one catalog")
    merged: Dict[str, Set[Tuple[int, ...]]] = {}
    configs = []
    for cat in catalogs:
        configs.append(cat.get("config", {}))
        for name, ent in cat.get("entries", {}).items():
            merged.setdefault(name, set()).update(
                tuple(int(x) for x in s) for s in ent.get("shapes", ()))
    entries = {name: {"shapes": [list(s) for s in sorted(shapes)],
                      "count": len(shapes)}
               for name, shapes in sorted(merged.items())}
    return {
        "version": 1,
        "config": {"union": configs},
        "entries": entries,
        "total_compilations": sum(e["count"] for e in entries.values()),
    }


def check_soundness(catalog: Dict, events: Sequence[Dict]) -> List[Finding]:
    """Diff observed compile events (CompileTracker.observed()) against a
    static catalog: any event whose (entry, shape) is not enumerated is a
    `shape-catalog-unsound` error naming the witness — the gate that
    keeps the enumeration honest (and that a deliberately shrunk catalog
    must fail)."""
    findings: List[Finding] = []
    entries = catalog.get("entries", {})
    for ev in events:
        name = ev.get("entry", "<unknown>")
        shape = tuple(int(x) for x in ev.get("shape", ()))
        known = {tuple(s) for s in entries.get(name, {}).get("shapes", ())}
        if shape not in known:
            findings.append(Finding(
                "shapecheck", "error", "shape-catalog-unsound",
                f"shapecheck:catalog/{name}",
                f"observed compile event for entry '{name}' at shape "
                f"{shape} is absent from the static catalog "
                f"(enumerated: {sorted(known) or 'no shapes'}) — the "
                f"enumeration missed a reachable launch shape; witness "
                f"event: {dict(ev)}"))
    return findings


# ---------------------------------------------------------------------------
# Registered pass

# The served configs the repo-level pass prices: the serve_generation
# defaults each decode path ships with (BASELINE-shaped, small enough to
# enumerate instantly). Override via AnalysisContext.shapecheck_configs.
DEFAULT_CONFIGS = {
    "paged_base": dict(slots=4, max_len=128, page_size=16,
                       prefill_chunk=32),
    "paged_spec": dict(slots=4, max_len=128, page_size=16,
                       prefill_chunk=32, spec_max_nodes=9, spec_depth=4),
    "dense": dict(slots=4, max_len=128, paged=False),
}


@register_pass("shapecheck")
def shapecheck_pass(ctx: AnalysisContext) -> List[Finding]:
    paths = ctx.src_paths if ctx.src_paths is not None else default_src_paths()
    findings = scan_paths(paths)
    budget = (int(ctx.shapecheck_budget) if ctx.shapecheck_budget
              else DEFAULT_SHAPE_BUDGET)
    configs = (ctx.shapecheck_configs if ctx.shapecheck_configs is not None
               else DEFAULT_CONFIGS)
    catalogs: Dict[str, Dict] = {}
    for name in sorted(configs):
        cat = enumerate_catalog(**configs[name])
        catalogs[name] = cat
        total = cat["total_compilations"]
        if total > budget:
            per = ", ".join(f"{e}={d['count']}"
                            for e, d in sorted(cat["entries"].items()))
            findings.append(Finding(
                "shapecheck", "warning", "shape-space-over-budget",
                f"shapecheck:config/{name}",
                f"config '{name}' reaches {total} distinct compilations "
                f"(> budget {budget}; {per}) — warmup pays one compile "
                "per shape, so either shrink the knobs (prefill_chunk, "
                "slots) or raise --shape-budget deliberately"))
    inventory: Dict[str, List[Dict]] = {}
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            try:
                inventory[os.path.basename(p)] = jit_entry_points(p)
            except SyntaxError:
                pass  # scan_file already reported it
    ctx.shapecheck_summary = {
        "budget": budget,
        "catalogs": catalogs,
        "entry_points": inventory,
    }
    findings.sort(key=lambda f: (f.severity != "error", f.where))
    return findings
