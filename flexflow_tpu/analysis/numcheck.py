"""numcheck — dtype-flow & scale-discipline static analysis, the
low-precision gate.

The lowered program is ground truth for numerics the same way it is for
collectives (hloaudit): a silent weak-type promotion or an unspecified
matmul accumulation dtype turns "int8 serving" into fp32 compute with
extra casts, and a quantized payload read that skips the scale sidecar
is garbage that still type-checks. Three arms:

  1. AST dtype-flow arm over the serving/compute hot paths (`paged/`,
     `spec/`, `runtime/executor.py`, `ops/`, `disagg/`): a dataflow
     lattice tracks array dtype provenance from creation sites
     (`.astype(jnp.int8)`, `jnp.zeros(..., dtype=int8)`,
     `quantize_leaf`, the pool's int8 payload) through assignments and
     calls, intra-function and deliberately OPTIMISTIC at unknowns
     (params, attributes, unrecognized calls are clean) — the same
     low-noise contract as shapecheck's taint arm.

  dtype-silent-promotion (error)   a low-precision payload (int8) or a
      forced f64 value meets float arithmetic / a float compute op with
      no explicit dequant or astype on the path. The finding carries
      the full derivation chain line by line (shapecheck's taint-chain
      idiom): int8 payload times a float is scale-less garbage; f64
      infects everything downstream at 2x HBM.
  scale-unpaired-access (error)    a `"k"`/`"v"` quantized payload read
      in a function that never touches the paired `k_scale`/`v_scale`
      sidecar — extends poolcheck's scale-sidecar invariant from page
      MOVEMENT to COMPUTE sites (metadata reads like `["k"].dtype` are
      exempt; mapping over every caches leaf counts as touching the
      sidecar by construction).
  dtype-accum-unspecified (warning) `dot`/`einsum`/`matmul` on operands
      known to be sub-fp32 (bf16/f16/fp8 provenance) without an
      explicit `preferred_element_type` — XLA may accumulate in the
      operand dtype and the error compounds over the contraction.
  dtype-cast-in-loop (info)        an `.astype(...)` inside a host
      `for`/`while` body — per-iteration casts are HBM traffic a hoist
      usually removes (observability only).
  stale-pragma (info)              a '# fflint: dtype-ok' pragma that
      no longer suppresses anything.

  Suppression: `# fflint: dtype-ok (reason)` on the flagged line or its
  enclosing loop header; the shared `# fflint: ignore` also applies.

  2. HLO numerics arm (runs when the CLI pairs numcheck with hloaudit:
     `--passes numcheck,hloaudit`): reuses hloaudit's lowering driver —
     each entry point's optimized HLO is scanned for `convert` ops and
     dot accumulation dtypes and diffed against the DECLARED per-entry
     dtype plan the Executor exports (`Executor.dtype_plan()`):

  hlo-unexpected-f64 (error)       f64 appears in a module whose plan
      forbids it (every plan does) — a weak-type promotion or stray
      np.float64 doubled the bytes of everything it touched.
  hlo-accum-downgrade (error)      a dot accumulates NARROWER than the
      plan's accumulation dtype — the mixed-precision win stopped
      being real. (A `dot`'s result type is its accumulator's on the
      CPU and GPU, whose modules this arm reads. The TPU's module has
      `convolution`s, accumulated f32 in the MXU whatever they hand
      out: there the type says where the sum is rounded, and the
      program names it where that matters, `ops/jax_ops.py`
      `contraction`; tests/test_column_group_tpu.py reads them.)
  hlo-unplanned-convert (warning)  convert traffic touching a float
      dtype outside the entry's declared dtype set, above the count
      band — casts the plan never budgeted.

  3. Tolerance-budget arm: validates the declarative numerics budget
     catalog (analysis/num_budgets.py) — every band positive/finite
     with a known kind and named consumers, required serving bands
     present (budget-invalid / budget-missing errors). The catalog is
     what the tests and the kv_quant_canary watchdog read, so numcheck
     failing here means a tolerance was edited out from under its
     consumers.

`dtype_flow_sites(path)` inventories the payload-read / accumulation /
cast sites the scan actually saw, so a gate test can prove a clean scan
engaged the hot paths (a clean scan of zero sites proves nothing).
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from typing import Dict, List, Optional, Sequence, Set, Tuple

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass

# The hot-path roots the AST arm audits, relative to the flexflow_tpu
# package root.
DEFAULT_SUBJECTS = ("paged", "spec", "runtime/executor.py", "ops",
                    "disagg")

# taint tags, widest-contamination first (join picks the worst)
_TAGS = ("f64", "int8", "lowfp")

_INT8_NAMES = {"int8", "i8"}
_LOWFP_NAMES = {"bfloat16", "bf16", "float16", "fp16", "half",
                "float8_e4m3fn", "float8_e5m2", "fp8"}
_F64_NAMES = {"float64", "f64", "double"}

# calls whose result is contraction/float compute: an int8 or f64
# operand reaching one of these is the promotion sink
_ACCUM_OPS = {"dot", "matmul", "einsum", "dot_general", "batch_matmul"}
_FLOAT_OPS = _ACCUM_OPS | {"softmax", "_dot_product_attention",
                           "dot_product_attention"}

# element-wise/structural calls that PROPAGATE their operand's taint
_PROPAGATE_CALLS = {"clip", "round", "abs", "negative", "where",
                    "maximum", "minimum", "reshape", "transpose",
                    "broadcast_to", "asarray", "squeeze",
                    "expand_dims", "concatenate", "stack"}

# creation calls that accept a dtype= (positional trailing or kw)
_CREATION_CALLS = {"zeros", "ones", "full", "empty", "array", "asarray",
                   "zeros_like", "ones_like", "full_like", "empty_like"}

# attribute reads that are METADATA, not payload (exempt from the
# scale-pairing rule: `bufs["k"].dtype` reads no quantized bytes)
_METADATA_ATTRS = {"dtype", "shape", "ndim", "size", "nbytes",
                   "itemsize", "sharding", "weak_type"}


def default_src_paths() -> List[str]:
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(base, p) for p in DEFAULT_SUBJECTS]


# ---------------------------------------------------------------------------
# pragma machinery (hostsync/shapecheck idiom)


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
    return directive.startswith("dtype-ok") or directive.startswith("ignore")


def _is_own_directive(txt: str) -> bool:
    """Only dtype-ok pragmas are OURS to flag stale — a shared
    '# fflint: ignore' may be earning its keep for another pass."""
    if "fflint:" not in txt:
        return False
    return txt.split("fflint:", 1)[1].strip().startswith("dtype-ok")


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


# ---------------------------------------------------------------------------
# AST dtype-flow arm


def _dtype_tag(node: ast.AST) -> Optional[str]:
    """The taint tag a dtype expression names: jnp.int8 / "int8" /
    np.float64 / jnp.bfloat16 ..., None for fp32/unknown."""
    name = None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value
    else:
        d = _dotted(node)
        if d:
            name = d[-1]
        elif isinstance(node, ast.Call):
            # jnp.dtype("int8") / np.dtype(np.float64)
            d = _dotted(node.func)
            if d and d[-1] == "dtype" and node.args:
                return _dtype_tag(node.args[0])
    if name in _INT8_NAMES:
        return "int8"
    if name in _LOWFP_NAMES:
        return "lowfp"
    if name in _F64_NAMES:
        return "f64"
    return None


def _join(*taints):
    """Worst tag wins; chains concatenate in argument order."""
    tag, chain = None, []
    for t in taints:
        if t is None:
            continue
        tt, tc = t
        chain = chain + list(tc)
        if tag is None or _TAGS.index(tt) < _TAGS.index(tag):
            tag = tt
    return (tag, chain) if tag is not None else None


class _DtypeScanner(ast.NodeVisitor):
    """Intra-function dtype-provenance dataflow. state maps a name to
    (tag, chain) where tag in {"int8", "lowfp", "f64"} and chain is
    [(lineno, description), ...] — the derivation the finding prints.
    OPTIMISTIC at unknowns: params, attributes and unrecognized calls
    are clean, so the errors are reserved for values that DEFINITELY
    carry low-precision/f64 provenance."""

    def __init__(self, findings, rel, comments, fn_name,
                 used_pragmas: Set[int], sites: Optional[List[Dict]] = None):
        self.findings = findings
        self.rel = rel
        self.comments = comments
        self.fn_name = fn_name
        self.loop_stack: List[int] = []
        self.used_pragmas = used_pragmas
        self.state: Dict[str, tuple] = {}
        self.sites = sites if sites is not None else []
        # creation sites already reported: one finding per derivation, not
        # one per downstream use (the chain replays the whole path anyway)
        self._reported: Set[tuple] = set()

    # -- classification ---------------------------------------------------

    def _classify(self, node: ast.AST):
        if isinstance(node, ast.Constant):
            return None
        if isinstance(node, ast.Name):
            return self.state.get(node.id)
        if isinstance(node, ast.Subscript):
            return self._classify(node.value)
        if isinstance(node, ast.Call):
            return self._classify_call(node)
        if isinstance(node, ast.BinOp):
            # sinks handled in visit_BinOp; propagation only here
            return _join(self._classify(node.left),
                         self._classify(node.right))
        if isinstance(node, ast.UnaryOp):
            return self._classify(node.operand)
        if isinstance(node, ast.IfExp):
            return _join(self._classify(node.body),
                         self._classify(node.orelse))
        if isinstance(node, ast.Tuple):
            return _join(*[self._classify(e) for e in node.elts])
        return None

    def _classify_call(self, node: ast.Call):
        d = _dotted(node.func)
        fname = d[-1] if d else None
        if fname == "astype" and isinstance(node.func, ast.Attribute):
            if node.args:
                tag = _dtype_tag(node.args[0])
                if tag is not None:
                    return (tag, [(node.lineno, _short(node))])
            # explicit cast to fp32/unknown: the dequant/astype the
            # promotion rule asks for — clears any taint
            return None
        if fname in ("set", "add", "max", "min", "mul", "get", "at"):
            # x.at[idx].set(v): the result is x's buffer (plus v)
            base = node.func
            while isinstance(base, (ast.Attribute, ast.Subscript,
                                    ast.Call)):
                base = getattr(base, "value", None) or \
                    getattr(base, "func", None)
                if base is None:
                    return None
            return _join(self._classify(base) if base is not None
                         else None,
                         *[self._classify(a) for a in node.args])
        if fname in _INT8_NAMES:
            return ("int8", [(node.lineno, _short(node))])
        if fname in _F64_NAMES:
            return ("f64", [(node.lineno, _short(node))])
        if fname in _LOWFP_NAMES or fname == "quantize_leaf":
            return ("lowfp", [(node.lineno, _short(node))])
        if fname == "dequantize_pages":
            return None  # scale-paired dequant: clean f32 by contract
        if fname == "quantized_append":
            return ("int8", [(node.lineno, _short(node))])
        if fname in _CREATION_CALLS:
            for kw in node.keywords:
                if kw.arg == "dtype":
                    tag = _dtype_tag(kw.value)
                    if tag is not None:
                        return (tag, [(node.lineno, _short(node))])
                    return None
            if node.args and fname.endswith("_like"):
                return self._classify(node.args[0])
            if len(node.args) >= 2 and not fname.endswith("_like"):
                tag = _dtype_tag(node.args[-1])
                if tag is not None:
                    return (tag, [(node.lineno, _short(node))])
            return None
        if fname in _PROPAGATE_CALLS:
            return _join(*[self._classify(a) for a in node.args])
        return None  # unknown call: optimistic

    # -- statement walking ------------------------------------------------

    def _assign_name(self, name: str, value: ast.AST, lineno: int):
        t = self._classify(value)
        if t is not None:
            tag, chain = t
            if not chain or chain[-1][0] != lineno:
                chain = list(chain) + [(lineno,
                                        f"{name} = {_short(value)}")]
            self.state[name] = (tag, chain)
        else:
            self.state.pop(name, None)

    def visit_Assign(self, node):
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                self._assign_name(tgt.id, node.value, node.lineno)
            elif isinstance(tgt, ast.Tuple):
                if isinstance(node.value, ast.Tuple) \
                        and len(tgt.elts) == len(node.value.elts):
                    for t, v in zip(tgt.elts, node.value.elts):
                        if isinstance(t, ast.Name):
                            self._assign_name(t.id, v, node.lineno)
                else:
                    t = self._classify(node.value)
                    for el in tgt.elts:
                        if isinstance(el, ast.Name):
                            if t is not None:
                                self.state[el.id] = t
                            else:
                                self.state.pop(el.id, None)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if isinstance(node.target, ast.Name):
            t = _join(self.state.get(node.target.id),
                      self._classify(node.value))
            if t is not None:
                self.state[node.target.id] = t
        self.generic_visit(node)

    # nested defs are separate scopes (same contract as shapecheck)
    def visit_FunctionDef(self, node):
        return

    visit_AsyncFunctionDef = visit_FunctionDef

    def _loop(self, node):
        self.loop_stack.append(node.lineno)
        self.generic_visit(node)
        self.loop_stack.pop()

    visit_For = visit_While = _loop

    def _add(self, severity, code, lineno, msg) -> bool:
        used = _suppressed(self.comments, lineno, *self.loop_stack)
        if used is not None:
            self.used_pragmas.add(used)
            return False
        self.findings.append(Finding(
            "numcheck", severity, code, f"{self.rel}:{lineno}",
            f"in {self.fn_name}(): {msg}"))
        return True

    def _trace(self, chain, lineno, tail: str) -> str:
        steps = list(chain)
        if not steps or steps[-1][0] != lineno:
            steps = steps + [(lineno, tail)]
        return " -> ".join(f"line {ln}: {d}" for ln, d in steps)

    def _promotion(self, taint, lineno, context: str):
        tag, chain = taint
        key = (tag, chain[0] if chain else lineno)
        if key in self._reported:
            return
        if tag == "f64":
            emitted = self._add(
                "error", "dtype-silent-promotion", lineno,
                f"f64 value reaches {context} — a float64 creation "
                "silently promotes everything downstream to 2x-width "
                "compute and HBM traffic; cast to float32 at the "
                f"source. derivation: {self._trace(chain, lineno, context)}")
        else:
            emitted = self._add(
                "error", "dtype-silent-promotion", lineno,
                f"low-precision (int8) payload meets {context} with no "
                "explicit dequant/astype on the path — int8 codes "
                "entering float math without their scale are garbage "
                "that still type-checks; dequantize (dequantize_pages / "
                "astype through the scale) first. derivation: "
                f"{self._trace(chain, lineno, context)}")
        if emitted:
            self._reported.add(key)

    _FLOAT_BINOPS = (ast.Mult, ast.Add, ast.Sub, ast.Div, ast.Pow,
                     ast.MatMult)

    def visit_BinOp(self, node):
        if isinstance(node.op, self._FLOAT_BINOPS):
            lt = self._classify(node.left)
            rt = self._classify(node.right)
            for own, other, other_node in ((lt, rt, node.right),
                                           (rt, lt, node.left)):
                if own is None:
                    continue
                tag = own[0]
                if tag == "f64":
                    self._promotion(own, node.lineno,
                                    f"arithmetic ({_short(node)})")
                    break
                float_const = (isinstance(other_node, ast.Constant)
                               and isinstance(other_node.value, float))
                if tag == "int8" and (float_const or
                                      isinstance(node.op, ast.MatMult)
                                      or (other is not None
                                          and other[0] != "int8")):
                    self._promotion(own, node.lineno,
                                    f"float arithmetic ({_short(node)})")
                    break
        self.generic_visit(node)

    def visit_Call(self, node):
        d = _dotted(node.func)
        fname = d[-1] if d else None
        if fname == "astype" and self.loop_stack:
            self.sites.append({"scope": self.fn_name,
                               "line": node.lineno, "kind": "cast"})
            self._add(
                "info", "dtype-cast-in-loop", node.lineno,
                f"`{_short(node)}` runs every iteration of the loop at "
                f"line {self.loop_stack[-1]} — a per-iteration cast is "
                "HBM traffic; hoist it out of the loop if the operand "
                "is loop-invariant")
        if fname in _FLOAT_OPS:
            self.sites.append({"scope": self.fn_name,
                               "line": node.lineno, "kind": "accum-op"})
            arg_taints = [(a, self._classify(a)) for a in node.args]
            worst = _join(*[t for _, t in arg_taints])
            if worst is not None and worst[0] in ("int8", "f64"):
                self._promotion(worst, node.lineno, f"{fname}()")
            elif worst is not None and worst[0] == "lowfp" \
                    and fname in _ACCUM_OPS \
                    and not any(kw.arg == "preferred_element_type"
                                for kw in node.keywords):
                self._add(
                    "warning", "dtype-accum-unspecified", node.lineno,
                    f"{fname}() on sub-fp32 operands without "
                    "preferred_element_type — XLA may accumulate in "
                    "the operand dtype and the error compounds over "
                    "the contraction; pass preferred_element_type="
                    "jnp.float32 (the ragged Pallas kernel's "
                    "discipline). derivation: "
                    f"{self._trace(worst[1], node.lineno, fname + '()')}")
        self.generic_visit(node)


# -- scale-pairing (function-level, not dataflow) ---------------------------


def _scan_scale_pairing(fn: ast.AST, rel: str, fn_name: str, comments,
                        used_pragmas: Set[int],
                        sites: Optional[List[Dict]] = None) -> List[Finding]:
    """scale-unpaired-access: a Load of `X["k"]` / `X["v"]` (the caches
    payload convention) in a function with NO sidecar evidence — no
    "_scale" string, no scale-named identifier, no call into the
    scale-aware quant helpers. Metadata reads (`["k"].dtype`) are
    exempt; so are nested defs (scanned as their own functions)."""
    parent: Dict[ast.AST, ast.AST] = {}
    own_nodes: List[ast.AST] = []

    def walk(node):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                continue
            own_nodes.append(child)
            walk(child)

    walk(fn)

    evidence = False
    reads: List[Tuple[int, str]] = []
    for node in own_nodes:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if "_scale" in node.value:
                evidence = True
        elif isinstance(node, ast.Name) and "scale" in node.id.lower():
            evidence = True
        elif isinstance(node, ast.Attribute) and \
                "scale" in node.attr.lower():
            evidence = True
        elif isinstance(node, ast.arg) and "scale" in node.arg.lower():
            evidence = True
        elif isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d and d[-1] in ("dequantize_pages", "quantized_append",
                               "scale_entry_names"):
                evidence = True
        if isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and isinstance(node.slice, ast.Constant) \
                and node.slice.value in ("k", "v"):
            par = parent.get(node)
            if isinstance(par, ast.Attribute) \
                    and par.attr in _METADATA_ATTRS:
                continue  # ["k"].dtype — metadata, no payload bytes
            reads.append((node.lineno, _short(node)))
            if sites is not None:
                sites.append({"scope": fn_name, "line": node.lineno,
                              "kind": "payload-read"})
    if evidence or not reads:
        return []
    findings: List[Finding] = []
    for lineno, txt in reads:
        used = _suppressed(comments, lineno)
        if used is not None:
            used_pragmas.add(used)
            continue
        findings.append(Finding(
            "numcheck", "error", "scale-unpaired-access",
            f"{rel}:{lineno}",
            f"in {fn_name}(): quantized payload read `{txt}` but this "
            "function never touches the k_scale/v_scale sidecar — on "
            "an int8 pool those codes are meaningless without their "
            "per-(page, head) scale (poolcheck guards the sidecar "
            "through page movement; compute sites must dequantize "
            "through it, or map over every caches leaf so the sidecar "
            "rides along)"))
    return findings


def dtype_flow_sites(path: str) -> List[Dict]:
    """The payload-read / accumulation-op / cast sites the scan saw in
    `path` ({scope, line, kind} per site) — the gate-test hook proving
    a clean scan actually engaged the hot paths."""
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    comments = _comment_map(src)
    sites: List[Dict] = []
    sink: List[Finding] = []
    used: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scanner = _DtypeScanner(sink, os.path.basename(path),
                                    comments, node.name, used,
                                    sites=sites)
            for child in node.body:
                scanner.visit(child)
            _scan_scale_pairing(node, os.path.basename(path), node.name,
                                comments, used, sites=sites)
    return sites


def scan_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    rel = rel or os.path.basename(path)
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("numcheck", "error", "syntax-error",
                        f"{rel}:{e.lineno}", str(e))]
    comments = _comment_map(src)
    findings: List[Finding] = []
    used_pragmas: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scanner = _DtypeScanner(findings, rel, comments, node.name,
                                    used_pragmas)
            for child in node.body:
                scanner.visit(child)
            findings += _scan_scale_pairing(node, rel, node.name,
                                            comments, used_pragmas)
    for ln, txt in sorted(comments.items()):
        if _is_own_directive(txt) and ln not in used_pragmas:
            findings.append(Finding(
                "numcheck", "info", "stale-pragma", f"{rel}:{ln}",
                "'# fflint: dtype-ok' pragma no longer suppresses any "
                "finding — delete it (stale annotations rot into "
                "blanket noise)"))
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
# HLO numerics arm (pairs with hloaudit's lowering driver)

# `%x = f32[8,16]{1,0} convert(bf16[8,16] %y)` — result dtype, operand
# dtype. Fusion bodies print the same instruction syntax, so converts
# inside fusions are counted line by line like hloaudit's transposes.
_CONVERT_RE = re.compile(
    r"%?[\w.\-]+ = (\w+)\[[^\]]*\]\S* convert\((\w+)\[")
# `%d = f32[...]{...} dot(...)` — the result dtype IS the accumulation
# dtype XLA committed to for this contraction (CPU / GPU modules; the
# TPU's has none, see hlo-accum-downgrade above)
_DOT_RE = re.compile(r"%?[\w.\-]+ = (\w+)\[[^\]]*\]\S* dot\(")
_F64_RE = re.compile(r"\bf64\[")

_FLOAT_DTS = {"f64", "f32", "bf16", "f16", "f8e4m3fn", "f8e5m2"}
_DT_WIDTH = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
             "f8e5m2": 1, "s8": 1}


def extract_numerics(txt: str) -> Dict:
    """Numerics summary of one optimized HLO module: convert-op pairs
    {(src, dst): count}, dot accumulation dtypes {dtype: count}, and
    the count of f64-typed results."""
    converts: Dict[Tuple[str, str], int] = {}
    dots: Dict[str, int] = {}
    f64 = 0
    for line in txt.splitlines():
        s = line.strip()
        if _F64_RE.search(s):
            f64 += 1
        m = _CONVERT_RE.match(s)
        if m:
            pair = (m.group(2), m.group(1))
            converts[pair] = converts.get(pair, 0) + 1
            continue
        m = _DOT_RE.match(s)
        if m:
            dots[m.group(1)] = dots.get(m.group(1), 0) + 1
    return {"converts": converts, "dots": dots, "f64_lines": f64}


def diff_dtype_plan(subject: str, entry: str, plan: Dict,
                    numerics: Dict, convert_band: int = 0
                    ) -> List[Finding]:
    """Diff one entry point's observed HLO numerics against its
    declared dtype plan ({"compute", "accum", "kv", "allowed",
    "allow_f64"} — Executor.dtype_plan()). `convert_band` is the count
    of out-of-plan float converts tolerated per dtype pair before the
    band warning fires."""
    findings: List[Finding] = []
    where = f"{subject}:{entry}" if subject else entry
    allowed = set(plan.get("allowed", ()))
    if plan.get("allow_f64", False):
        # an explicit f64 allowance also budgets casts into/out of it
        allowed = allowed | {"f64"}
    accum = plan.get("accum", "f32")
    accum_w = _DT_WIDTH.get(accum, 4)

    if numerics.get("f64_lines", 0) and not plan.get("allow_f64", False):
        findings.append(Finding(
            "numcheck", "error", "hlo-unexpected-f64", where,
            f"{numerics['f64_lines']} f64-typed instruction(s) in the "
            f"lowered module but the dtype plan declares no f64 "
            f"(plan dtypes: {sorted(allowed) or '(none)'}) — a silent "
            "weak-type promotion (bare Python float / np.float64) is "
            "doubling compute and HBM bytes; pin the scalar's dtype at "
            "the source"))

    for dt, count in sorted(numerics.get("dots", {}).items()):
        if _DT_WIDTH.get(dt, 4) < accum_w:
            findings.append(Finding(
                "numcheck", "error", "hlo-accum-downgrade", where,
                f"{count} dot(s) accumulate at {dt}, narrower than the "
                f"plan's accumulation dtype {accum} — the contraction "
                "error compounds in the operand dtype; set "
                "preferred_element_type at the call site (witness: "
                f"dot result dtypes {numerics['dots']})"))

    unplanned = {pair: n for pair, n in
                 sorted(numerics.get("converts", {}).items())
                 if (pair[0] in _FLOAT_DTS or pair[1] in _FLOAT_DTS)
                 and not ({pair[0], pair[1]} & _FLOAT_DTS <= allowed)}
    for (src, dst), count in unplanned.items():
        if count > convert_band:
            findings.append(Finding(
                "numcheck", "warning", "hlo-unplanned-convert", where,
                f"{count} convert(s) {src} -> {dst} touch a float "
                f"dtype outside the entry's declared plan "
                f"{sorted(allowed)} (band: {convert_band}) — casts the "
                "plan never budgeted; either extend the Executor dtype "
                "plan or remove the stray cast"))
    return findings


# ---------------------------------------------------------------------------
# tolerance-budget arm


def budget_findings() -> List[Finding]:
    from flexflow_tpu.analysis.num_budgets import validate_catalog

    findings: List[Finding] = []
    for name, problem in sorted(validate_catalog().items()):
        code = ("budget-missing" if problem.startswith("<missing>")
                else "budget-invalid")
        findings.append(Finding(
            "numcheck", "error", code,
            f"analysis/num_budgets.py:{name}",
            f"numerics budget {name!r}: {problem} — the catalog is "
            "what the tolerance tests and the kv_quant_canary "
            "watchdog dereference; fix the band, do not orphan its "
            "consumers"))
    return findings


# ---------------------------------------------------------------------------
# registered pass


@register_pass("numcheck")
def numcheck_pass(ctx: AnalysisContext) -> List[Finding]:
    """Two modes, keyed on the context (pass-registry contract):

    - ctx.hlo_modules present (the CLI's `--passes numcheck,hloaudit`
      per-subject contexts): HLO numerics arm only — diff each entry's
      lowered module against ctx.numcheck_dtype_plan; skips silently
      when the plan is absent.
    - otherwise (default invocation): AST dtype-flow arm over
      ctx.src_paths (default: the hot-path roots) plus the
      tolerance-budget arm.
    """
    if ctx.hlo_modules is not None:
        plan = ctx.numcheck_dtype_plan
        if plan is None:
            return []
        band = (int(ctx.numcheck_convert_band)
                if ctx.numcheck_convert_band is not None else 0)
        findings: List[Finding] = []
        observed: Dict[str, Dict] = {}
        for entry, mod in sorted(ctx.hlo_modules.items()):
            if mod.get("error"):
                continue  # hloaudit already reports hlo-entry-failed
            eplan = plan.get(entry)
            if eplan is None:
                continue
            num = extract_numerics(mod["hlo_text"])
            findings += diff_dtype_plan(ctx.subject, entry, eplan, num,
                                        convert_band=band)
            observed[entry] = {
                "plan": eplan,
                "dots": dict(num["dots"]),
                "converts": {f"{s}->{d}": n for (s, d), n
                             in sorted(num["converts"].items())},
                "f64_lines": num["f64_lines"],
            }
        if ctx.numcheck_summary is None:
            ctx.numcheck_summary = {}
        ctx.numcheck_summary[ctx.subject or "module"] = observed
        return findings

    paths = (ctx.src_paths if ctx.src_paths is not None
             else default_src_paths())
    findings = scan_paths(paths)
    findings += budget_findings()
    from flexflow_tpu.analysis.num_budgets import BUDGETS

    inventory: Dict[str, int] = {"payload-read": 0, "accum-op": 0,
                                 "cast": 0}
    nfiles = 0
    for p in paths:
        files = []
        if os.path.isdir(p):
            for dirpath, _dirs, fns in os.walk(p):
                files += [os.path.join(dirpath, fn) for fn in fns
                          if fn.endswith(".py")]
        elif os.path.exists(p):
            files = [p]
        for f in files:
            nfiles += 1
            try:
                for s in dtype_flow_sites(f):
                    inventory[s["kind"]] = inventory.get(s["kind"], 0) + 1
            except SyntaxError:
                pass  # scan_file already reported it
    ctx.numcheck_summary = {
        "files_scanned": nfiles,
        "sites": inventory,
        "budgets": len(BUDGETS),
    }
    findings.sort(key=lambda f: (f.severity != "error", f.where))
    return findings
