"""Retrace/host-sync lint — an AST pass over the serving/runtime hot
paths (`runtime/`, `serving.py`, `paged/`, `spec/`).

Flags jit-boundary hazards in DIRECT function bodies (v1 is deliberately
non-transitive — it reads each function's own AST, not its callees):

  item-sync-in-loop   (error)   `.item()` inside a loop: a per-element
      device sync in a decode hot loop serializes the pipeline; pull the
      whole batch once with np.asarray outside the per-token loop.
  jnp-in-host-loop    (warning) `jnp.*`/`jax.numpy.*` calls inside a
      loop of a NON-jitted function: each call dispatches to the device
      from host code — per-token loops pay a dispatch per step.
  asarray-in-loop     (info)    `np.asarray`/`np.array`/`jax.device_get`
      inside a loop: a bulk sync per iteration — fine once per decode
      tick, a hazard per token (observability; judge by loop granularity).
  shape-branch-in-jit (warning) an `if`/`while` on `.shape`/`.ndim`
      inside a jit-wrapped function: shapes are trace-time constants, so
      the branch recompiles per shape class (fine for deliberate kernel
      selection, a retrace storm when shapes vary per request).
  device-loop         (error)   a host sync inside the body/cond of a
      `lax.while_loop`/`fori_loop`/`scan`: `.item()`, `np.*`/`numpy.*`
      calls, `jax.device_get` or a host callback
      (`pure_callback`/`io_callback`) in a traced device-loop body
      either fails on tracers or silently re-enters the host mid-loop,
      so this rule takes no pragma suppression.
      `device_loop_bodies(path)` reports which bodies were analyzed, so
      a gate test can assert the rule engaged (a clean result proves
      nothing if no loop was seen).

Suppression: any flagged line (or its enclosing loop header) carrying a
`# fflint: host-ok` / `# fflint: ignore` comment is skipped — intentional
per-tick syncs are annotated, not silenced globally. A directive that no
longer suppresses ANY finding is itself flagged:

  stale-pragma        (info)    the annotated hazard was refactored away
      but the pragma survived — delete it so annotations keep meaning
      something (suppressions must not rot into blanket noise).
"""

from __future__ import annotations

import ast
import io
import os
import tokenize
from typing import Dict, List, Optional, Set

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass

DEFAULT_ROOTS = ("runtime", "serving.py", "paged", "spec", "obs",
                 "serving_autopilot.py")

_SYNC_CALLS = {("np", "asarray"), ("np", "array"), ("numpy", "asarray"),
               ("numpy", "array"), ("jax", "device_get")}
_DEVICE_MODULES = {"jnp", "lax"}

# structured-control-flow primitives whose function arguments trace as
# DEVICE loop bodies (argument index of each body-like callable)
_DEVICE_LOOP_FNS = {"while_loop": (0, 1), "fori_loop": (2,), "scan": (0,)}
_HOST_MODULES = {"np", "numpy"}
_HOST_CALLBACKS = {"pure_callback", "io_callback", "device_get"}


def default_src_paths() -> List[str]:
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(base, p) for p in DEFAULT_ROOTS]


def _dotted(node: ast.AST) -> Optional[tuple]:
    """('np', 'asarray') for np.asarray, ('jnp', 'sum') for jnp.sum."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _jitted_names(tree: ast.Module) -> Set[str]:
    """Function names wrapped by jax.jit in this module: decorated
    defs and `jax.jit(step)` call sites naming a local function."""
    jitted: Set[str] = set()

    def is_jit(expr: ast.AST) -> bool:
        d = _dotted(expr)
        if d and d[-1] == "jit":
            return True
        if isinstance(expr, ast.Call):
            # partial(jax.jit, ...) / jax.jit(fn, static_argnums=...)
            if is_jit(expr.func):
                return True
            return any(is_jit(a) for a in expr.args)
        return False

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(is_jit(dec) for dec in node.decorator_list):
                jitted.add(node.name)
        elif isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d and d[-1] == "jit" and node.args \
                    and isinstance(node.args[0], ast.Name):
                jitted.add(node.args[0].id)
    return jitted


def _is_directive(txt: str) -> bool:
    if "fflint:" not in txt:
        return False
    # only the exact directives suppress — a comment like
    # '# fflint: broken, fix this' must NOT count
    directive = txt.split("fflint:", 1)[1].strip()
    return directive.startswith("host-ok") or directive.startswith("ignore")


def _comment_map(src: str) -> Dict[int, str]:
    """lineno -> COMMENT token text. Directives must live in actual
    comments: a docstring that merely *documents* the
    '# fflint: host-ok' convention is neither a suppression nor a stale
    pragma."""
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
    """The line number of the directive that suppresses a finding on any
    of `linenos` (the flagged line or its enclosing loop headers), or
    None. Returning the LINE lets the caller track which pragmas earned
    their keep — unused ones are flagged stale."""
    for ln in linenos:
        if _is_directive(comments.get(ln, "")):
            return ln
    return None


class _FnScanner(ast.NodeVisitor):
    """Scan ONE function body (nested defs get their own scanner)."""

    def __init__(self, findings, rel, comments, fn_name, jitted,
                 used_pragmas: Optional[Set[int]] = None):
        self.findings = findings
        self.rel = rel
        self.comments = comments
        self.fn_name = fn_name
        self.jitted = fn_name in jitted
        self.loop_stack: List[int] = []  # header linenos
        self.used_pragmas = used_pragmas if used_pragmas is not None \
            else set()

    def _add(self, severity, code, lineno, msg):
        used = _suppressed(self.comments, lineno, *self.loop_stack)
        if used is not None:
            self.used_pragmas.add(used)
            return
        self.findings.append(Finding(
            "hostsync", severity, code, f"{self.rel}:{lineno}",
            f"in {self.fn_name}(): {msg}"))

    # nested function definitions are separate scopes — do not inherit
    # the enclosing loop stack (a closure defined in a loop runs later)
    def visit_FunctionDef(self, node):
        return

    visit_AsyncFunctionDef = visit_FunctionDef

    def _loop(self, node):
        self.loop_stack.append(node.lineno)
        self.generic_visit(node)
        self.loop_stack.pop()

    visit_For = visit_While = _loop

    def _test_touches_shape(self, test: ast.AST) -> bool:
        return any(isinstance(n, ast.Attribute)
                   and n.attr in ("shape", "ndim")
                   for n in ast.walk(test))

    def visit_If(self, node):
        if self.jitted and self._test_touches_shape(node.test):
            self._add(
                "warning", "shape-branch-in-jit", node.lineno,
                "branch on .shape/.ndim inside a jitted function — the "
                "branch re-traces per shape class; hoist the decision "
                "out of the jitted fn or make it a static_argnum")
        self.generic_visit(node)

    def visit_While(self, node):
        if self.jitted and self._test_touches_shape(node.test):
            self._add(
                "warning", "shape-branch-in-jit", node.lineno,
                "while on .shape/.ndim inside a jitted function")
        self._loop(node)

    def visit_Call(self, node):
        in_loop = bool(self.loop_stack)
        d = _dotted(node.func)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args and in_loop:
            self._add(
                "error", "item-sync-in-loop", node.lineno,
                ".item() inside a loop is a per-element device sync — in "
                "a decode hot loop it serializes host and device every "
                "token; read the whole batch once with np.asarray "
                "outside the loop (annotate '# fflint: host-ok' if this "
                "loop is genuinely not per-token)")
        elif d and in_loop and not self.jitted:
            if d[:2] in _SYNC_CALLS:
                self._add(
                    "info", "asarray-in-loop", node.lineno,
                    f"{'.'.join(d)} inside a loop — one bulk device sync "
                    "per iteration (fine per decode tick, a hazard per "
                    "token)")
            elif d[0] in _DEVICE_MODULES or d[:2] == ("jax", "numpy"):
                self._add(
                    "warning", "jnp-in-host-loop", node.lineno,
                    f"{'.'.join(d)} inside a host-side loop dispatches "
                    "to the device each iteration — batch it, move the "
                    "loop into jit/scan, or annotate '# fflint: host-ok' "
                    "for a deliberate per-tick transfer")
        self.generic_visit(node)


class _DeviceLoopScanner(ast.NodeVisitor):
    """Scan one lax.while_loop/fori_loop/scan body for host syncs. No
    pragma suppression: a sync inside a traced device loop is never an
    intentional per-tick transfer — it is a bug (trace failure or a
    host re-entry mid-loop)."""

    def __init__(self, findings, rel, kind, body_name):
        self.findings = findings
        self.rel = rel
        self.where = f"{kind} body {body_name!r}"

    def _add(self, lineno, msg):
        self.findings.append(Finding(
            "hostsync", "error", "device-loop", f"{self.rel}:{lineno}",
            f"in {self.where}: {msg}"))

    # a def nested inside a loop body still traces as part of it when
    # called there — v1 stays direct-body like the rest of the pass, so
    # nested defs are skipped (documented non-transitivity)
    def visit_FunctionDef(self, node):
        return

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        d = _dotted(node.func)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args:
            self._add(node.lineno,
                      ".item() — a per-element device sync cannot trace "
                      "inside a device loop body")
        elif d and d[0] in _HOST_MODULES:
            self._add(node.lineno,
                      f"{'.'.join(d)} — numpy executes on host at trace "
                      "time; inside a device loop it fails on tracers or "
                      "bakes a stale constant")
        elif d and d[-1] in _HOST_CALLBACKS and d[0] == "jax":
            self._add(node.lineno,
                      f"{'.'.join(d)} — a host round-trip inside the "
                      "device loop defeats the fused dispatch")
        self.generic_visit(node)


def _device_loop_scan(tree: ast.Module, rel: str, findings: List[Finding],
                      bodies: Optional[List[Dict]] = None) -> None:
    """Find every lax.while_loop/fori_loop/scan call site, resolve its
    body-like arguments (local function names or inline lambdas), and
    scan each body for host syncs. `bodies` collects what was analyzed
    (for device_loop_bodies / gate tests)."""
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if not d or d[-1] not in _DEVICE_LOOP_FNS or "lax" not in d:
            continue
        kind = d[-1]
        for idx in _DEVICE_LOOP_FNS[kind]:
            if idx >= len(node.args):
                continue
            arg = node.args[idx]
            targets = []
            if isinstance(arg, ast.Name):
                # same-name defs elsewhere in the module are scanned
                # too — an over-approximation a lint can afford
                targets = [(arg.id, fn) for fn in defs.get(arg.id, ())]
            elif isinstance(arg, ast.Lambda):
                targets = [("<lambda>", arg)]
            for name, fn in targets:
                if bodies is not None:
                    bodies.append({"kind": kind, "body": name,
                                   "line": node.lineno})
                scanner = _DeviceLoopScanner(findings, rel, kind, name)
                body = fn.body if isinstance(fn.body, list) else [fn.body]
                for child in body:
                    scanner.visit(child)


def device_loop_bodies(path: str) -> List[Dict]:
    """The device-loop bodies the `device-loop` rule analyzed in `path`
    ({kind, body, line} per body). A gate test pairs this with
    scan_file: zero device-loop findings only proves something when at
    least one body was actually seen."""
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    bodies: List[Dict] = []
    _device_loop_scan(tree, os.path.basename(path), [], bodies)
    return bodies


def scan_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    rel = rel or os.path.basename(path)
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("hostsync", "error", "syntax-error",
                        f"{rel}:{e.lineno}", str(e))]
    comments = _comment_map(src)
    jitted = _jitted_names(tree)
    findings: List[Finding] = []
    used_pragmas: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scanner = _FnScanner(findings, rel, comments, node.name,
                                 jitted, used_pragmas)
            for child in node.body:
                scanner.visit(child)
    _device_loop_scan(tree, rel, findings)
    # suppression hygiene: a directive that silenced nothing is stale —
    # the hazard it annotated was refactored away and the annotation must
    # not survive to blanket-silence a future real finding
    for ln, txt in sorted(comments.items()):
        if _is_directive(txt) and ln not in used_pragmas:
            findings.append(Finding(
                "hostsync", "info", "stale-pragma", f"{rel}:{ln}",
                "'# fflint: host-ok' pragma no longer suppresses any "
                "finding — delete it (stale annotations rot into blanket "
                "noise)"))
    findings.sort(key=lambda f: f.where)
    return findings


def scan_paths(paths: List[str]) -> List[Finding]:
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


@register_pass("hostsync")
def hostsync_pass(ctx: AnalysisContext) -> List[Finding]:
    paths = ctx.src_paths if ctx.src_paths is not None else default_src_paths()
    return scan_paths(paths)
