"""racecheck — lock-discipline analysis + bounded interleaving model
checking for the threaded serving protocols (the seventh fflint pass).

PRs 16-17 made the server genuinely concurrent: prefill/decode workers
hand live requests through a shared HostTier, PrefixAffinityRouter
mutates affinity/load maps from caller threads, and ServingAutopilot
drains-and-swaps a running server under `_swap_lock`. This pass checks
that concurrency two ways, mirroring poolcheck's lint + model-check
split:

  STATIC ARM — a whole-repo lock model over serving.py,
      paged/scheduler.py, spec/server.py, disagg/, serving_autopilot.py
      and obs/. Every `self._*lock`-style attribute is a lock; a field
      written under lock L on ANY path is L-guarded; thread contexts
      come from entry-point discovery (`threading.Thread(target=...)`
      methods and the intra-class call graph they reach, vs the public
      caller surface). Rules:

  race-unguarded-write   (error)   a guarded field written lock-free
      where another thread context also touches it (or anywhere, for a
      shared object with no thread of its own).
  lock-order-cycle       (error)   a cycle in the cross-file
      lock-acquisition-order graph (lock held while a method that
      takes another lock is called, resolved one call level deep).
  lock-held-device-sync  (warning) device_get / block_until_ready /
      thread join / future result / event wait while holding a lock —
      the drain-stall class, one call level deep.
  atomicity-split        (warning) a method reads a guarded field
      under a lock, releases it, and re-acquires the same lock to
      write that field — check-then-act across a lock release.
  stale-pragma           (info)    a race-ok pragma suppressing nothing.

  Pragmas: `# fflint: race-ok (reason)` on the flagged line or its
  `def` line.

  DYNAMIC ARM — explore_interleavings(): a bounded explicit-state
      checker over abstract labeled-transition-system models of the
      cross-thread protocols, with per-thread program counters:
      `handoff` (prefill→decode handoff through the shared tier),
      `tierpool` (concurrent spill/fetch/admission on a pool pair with
      LRU capacity drops), `swap` (drain-and-swap under live submits,
      the swap lock modeled explicitly), and `launch_ahead` (the
      one-deep launch pipeline: host admission racing the launch in
      flight, its picks taken a launch late behind a fence). All
      interleavings up to a context-switch bound
      (DEFAULT_SWITCH_BOUND) are explored with DPOR-style sleep-set
      pruning over declared action read/write footprints;
      PROTOCOL_INVARIANTS (future never dropped, request owned by
      exactly one worker, tier partition holds mid-fetch, no swap while
      a handoff is in flight, single token-buffer owner and no
      stale-table bookkeeping across the dispatch fence, plus abstract
      mirrors of the poolcheck catalog's conservation and accounting)
      are asserted at every state. A violation reports the MINIMAL
      interleaving (BFS order), replayable via replay_interleaving();
      seeded mutations (double_submit, unlocked_submit,
      no_safepoint_join, fetch_no_remove, read_before_fence,
      free_at_late_stop, defrag_without_fence) prove the gate can fail.

poolcheck's `unlocked-cross-thread-read` lint delegates to
build_lock_model() here, so there is exactly ONE lock model in the
tree. CLI: tools/fflint.py runs racecheck by default; `--since` keeps
the static arm only. See docs/analysis.md for finding kinds, pragma
form, the protocol models, and bound semantics.
"""

from __future__ import annotations

import ast
import copy
import io
import json
import os
import tokenize
from collections import deque
from typing import Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass

# ---------------------------------------------------------------------------
# shared helpers (poolcheck's comment/dotted idioms, local so the
# dependency points poolcheck -> racecheck, never back)

_DIRECTIVES = ("race-ok",)

RACE_ROOTS = ("serving.py", os.path.join("paged", "scheduler.py"),
              os.path.join("spec", "server.py"), "disagg",
              "serving_autopilot.py", "obs")

# methods that run before (or outside) any concurrent phase of the
# object's life — construction and pickling are single-threaded by
# contract, so their lock-free writes are not races
_LIFECYCLE_METHODS = {"__init__", "__new__", "__getstate__",
                      "__setstate__", "__reduce__", "__del__",
                      "__deepcopy__", "__copy__"}


def default_lint_paths() -> List[str]:
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(base, p) for p in RACE_ROOTS]


def _dotted(node: ast.AST) -> Optional[tuple]:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _directive_of(txt: str) -> Optional[str]:
    if "fflint:" not in txt:
        return None
    d = txt.split("fflint:", 1)[1].strip()
    for name in _DIRECTIVES:
        if d.startswith(name):
            return name
    return None


def _comment_map(src: str) -> Dict[int, str]:
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError):
        pass
    return out


class _RFileLint:
    """Per-file lint state: comments, race-ok pragma bookkeeping,
    findings (the poolcheck _FileLint shape, pass_name racecheck)."""

    def __init__(self, rel: str, src: str, tree: ast.Module):
        self.rel = rel
        self.tree = tree
        self.comments = _comment_map(src)
        self.used_pragmas: Set[int] = set()
        self.findings: List[Finding] = []

    def add(self, severity: str, code: str, lineno: int, msg: str,
            *extra_linenos: int):
        for ln in (lineno,) + extra_linenos:
            if _directive_of(self.comments.get(ln, "")) is not None:
                self.used_pragmas.add(ln)
                return
        self.findings.append(Finding(
            "racecheck", severity, code, f"{self.rel}:{lineno}", msg))

    def stale_pragmas(self):
        for ln, txt in sorted(self.comments.items()):
            if _directive_of(txt) is not None \
                    and ln not in self.used_pragmas:
                self.findings.append(Finding(
                    "racecheck", "info", "stale-pragma",
                    f"{self.rel}:{ln}",
                    "'# fflint: race-ok' pragma no longer suppresses "
                    "any racecheck finding — delete it"))


# ---------------------------------------------------------------------------
# the lock model (shared with poolcheck's unlocked-cross-thread-read)

def _is_lock_attr(name: str) -> bool:
    return name.startswith("_") and name.endswith("lock")


def _lock_with_attrs(node: ast.With) -> List[str]:
    out = []
    for item in node.items:
        d = _dotted(item.context_expr)
        if d and len(d) == 2 and d[0] == "self" and _is_lock_attr(d[1]):
            out.append(d[1])
    return out


# blocking-call matchers for lock-held-device-sync: name -> a predicate
# on the dotted base (None = any base); `join`/`result` need a
# thread/future-looking receiver so `", ".join(...)` stays quiet
_BLOCKING = {
    "device_get": None,
    "block_until_ready": None,
    "wait": None,
    "sleep": None,
    "join": lambda base: any("thread" in seg.lower() for seg in base),
    "result": lambda base: any("fut" in seg.lower() for seg in base),
}


class Access(NamedTuple):
    field: str
    lineno: int
    held: FrozenSet[str]      # lock attrs held at the access


class CallSite(NamedTuple):
    dotted: tuple
    lineno: int
    held: FrozenSet[str]
    # the call is a `return <call>` — nothing in this method runs after
    # it, so it can never be the EARLIER half of an atomicity split
    in_return: bool = False


class Region(NamedTuple):
    """One `with self.<lock>:` block: its own field traffic plus the
    self-method calls made inside it (expanded one level by rules)."""

    attr: str
    lineno: int
    held_before: FrozenSet[str]
    reads: FrozenSet[str]
    writes: FrozenSet[str]
    calls: Tuple[str, ...]    # same-class method names called inside


class MethodSummary:
    __slots__ = ("name", "lineno", "reads", "writes", "regions", "calls",
                 "blocking", "thread_targets")

    def __init__(self, name: str, lineno: int):
        self.name = name
        self.lineno = lineno
        self.reads: List[Access] = []
        self.writes: List[Access] = []
        self.regions: List[Region] = []
        self.calls: List[CallSite] = []
        self.blocking: List[Tuple[str, int, FrozenSet[str]]] = []
        self.thread_targets: List[str] = []

    def self_calls(self) -> List[str]:
        return [c.dotted[1] for c in self.calls
                if len(c.dotted) == 2 and c.dotted[0] == "self"]


class _MethodScan(ast.NodeVisitor):
    """One pass over a method body: field accesses with the held-lock
    set, with-regions, calls, blocking calls, Thread targets. Nested
    defs are separate execution contexts (scanned on demand when they
    turn out to be Thread targets)."""

    def __init__(self, summary: MethodSummary):
        self.s = summary
        self.held: List[str] = []
        self.open_regions: List[dict] = []
        self._in_return = False

    # -- bookkeeping -------------------------------------------------------

    def _heldset(self) -> FrozenSet[str]:
        return frozenset(self.held)

    def _read(self, field: str, lineno: int):
        self.s.reads.append(Access(field, lineno, self._heldset()))
        for r in self.open_regions:
            r["reads"].add(field)

    def _write(self, field: str, lineno: int):
        self.s.writes.append(Access(field, lineno, self._heldset()))
        for r in self.open_regions:
            r["writes"].add(field)

    def _write_target(self, t: ast.AST, lineno: int):
        for el in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                   else [t]):
            base = el
            is_sub = isinstance(el, ast.Subscript)
            if is_sub:
                base = el.value
            d = _dotted(base)
            if d and len(d) == 2 and d[0] == "self":
                self._write(d[1], lineno)
                if is_sub:           # self._x[k] = v reads _x to index it
                    self._read(d[1], lineno)
            elif is_sub:
                self.visit(el.value)
            if is_sub and el.slice is not None:
                self.visit(el.slice)

    # -- visitors ----------------------------------------------------------

    def visit_FunctionDef(self, node):
        return  # nested defs are deferred contexts

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_With(self, node):
        attrs = _lock_with_attrs(node)
        if not attrs:
            self.generic_visit(node)
            return
        rec = dict(attrs=attrs, lineno=node.lineno,
                   held_before=self._heldset(),
                   reads=set(), writes=set(), calls=[])
        self.held.extend(attrs)
        self.open_regions.append(rec)
        for stmt in node.body:
            self.visit(stmt)
        self.open_regions.pop()
        del self.held[-len(attrs):]
        for a in attrs:
            self.s.regions.append(Region(
                a, rec["lineno"], rec["held_before"],
                frozenset(rec["reads"]), frozenset(rec["writes"]),
                tuple(rec["calls"])))

    def visit_Assign(self, node):
        for t in node.targets:
            self._write_target(t, node.lineno)
        self.visit(node.value)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._write_target(node.target, node.lineno)
            self.visit(node.value)

    def visit_AugAssign(self, node):
        self._write_target(node.target, node.lineno)
        d = _dotted(node.target.value if isinstance(
            node.target, ast.Subscript) else node.target)
        if d and len(d) == 2 and d[0] == "self":
            self._read(d[1], node.lineno)  # x += 1 reads then writes
        self.visit(node.value)

    def visit_Delete(self, node):
        for t in node.targets:
            self._write_target(t, node.lineno)

    def visit_Return(self, node):
        if node.value is not None:
            self._in_return = True
            self.visit(node.value)
            self._in_return = False

    def visit_Call(self, node):
        d = _dotted(node.func)
        if d:
            self.s.calls.append(CallSite(d, node.lineno, self._heldset(),
                                         self._in_return))
            for r in self.open_regions:
                if len(d) == 2 and d[0] == "self":
                    r["calls"].append(d[1])
            name = d[-1]
            pred = _BLOCKING.get(name)
            if name in _BLOCKING and (pred is None or pred(d[:-1])) \
                    and self.held:
                self.s.blocking.append(
                    (".".join(d), node.lineno, self._heldset()))
            if name == "Thread":
                for kw in node.keywords:
                    if kw.arg == "target":
                        td = _dotted(kw.value)
                        if td and td[0] == "self" and len(td) == 2:
                            self.s.thread_targets.append(td[1])
                        elif isinstance(kw.value, ast.Name):
                            self.s.thread_targets.append(kw.value.id)
            # a self-method call reads no field; self._x.m() reads _x
            if d[0] == "self" and len(d) >= 3:
                self._read(d[1], node.lineno)
        else:
            self.visit(node.func)
        for a in node.args:
            self.visit(a)
        for kw in node.keywords:
            self.visit(kw.value)

    def visit_Attribute(self, node):
        if isinstance(node.ctx, ast.Load):
            d = _dotted(node)
            if d and d[0] == "self" and len(d) >= 2:
                self._read(d[1], node.lineno)
                return
        self.generic_visit(node)


def _scan_method(node, name: Optional[str] = None) -> MethodSummary:
    s = MethodSummary(name or node.name, node.lineno)
    scan = _MethodScan(s)
    for stmt in node.body:
        scan.visit(stmt)
    return s


def _owned_fields(node: ast.ClassDef) -> Set[str]:
    """poolcheck's historical `owned` semantics, verbatim: fields
    assigned anywhere inside a PRIVATE method (nested defs included)."""
    owned: Set[str] = set()
    for meth in node.body:
        if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        private = meth.name.startswith("_") \
            and not meth.name.startswith("__")
        if not private:
            continue
        for sub in ast.walk(meth):
            if isinstance(sub, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = sub.targets if isinstance(sub, ast.Assign) \
                    else [sub.target]
                for t in targets:
                    for el in (t.elts if isinstance(
                            t, (ast.Tuple, ast.List)) else [t]):
                        base = el.value if isinstance(
                            el, ast.Subscript) else el
                        d = _dotted(base)
                        if d and len(d) == 2 and d[0] == "self":
                            owned.add(d[1])
    return owned


class ClassModel:
    __slots__ = ("name", "rel", "node", "bases", "lock_attrs", "methods",
                 "owned", "entry_names")

    def __init__(self, rel: str, node: ast.ClassDef):
        self.name = node.name
        self.rel = rel
        self.node = node
        self.bases = [d[-1] for d in
                      (_dotted(b) for b in node.bases) if d]
        self.owned = _owned_fields(node)
        self.lock_attrs: Dict[str, str] = {}  # attr -> "lock"|"rlock"
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call):
                d = _dotted(sub.value.func)
                if d and d[-1] in ("Lock", "RLock"):
                    for t in sub.targets:
                        td = _dotted(t)
                        if td and len(td) == 2 and td[0] == "self":
                            self.lock_attrs[td[1]] = \
                                "rlock" if d[-1] == "RLock" else "lock"
        self.methods: Dict[str, MethodSummary] = {}
        self.entry_names: Set[str] = set()
        for meth in node.body:
            if not isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            s = _scan_method(meth)
            self.methods[meth.name] = s
            # Thread(target=<nested fn>) — scan the nested body as a
            # pseudo-method in loop context (the autopilot controller)
            for tgt in s.thread_targets:
                if tgt in self.methods or any(
                        m.name == tgt for m in node.body
                        if isinstance(m, ast.FunctionDef)):
                    self.entry_names.add(tgt)
                    continue
                for sub in ast.walk(meth):
                    if isinstance(sub, ast.FunctionDef) \
                            and sub.name == tgt:
                        pname = f"{meth.name}.<locals>.{tgt}"
                        self.methods[pname] = _scan_method(sub, pname)
                        self.entry_names.add(pname)

    @property
    def threaded(self) -> bool:
        return bool(self.entry_names) or any(
            s.regions for s in self.methods.values())

    def public_method_nodes(self):
        for meth in self.node.body:
            if isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and not meth.name.startswith("_"):
                yield meth


class LockModel:
    """All classes across the scanned files, with the hierarchy closed
    both ways (a subclass's loop thread races the base's public
    readers, and vice versa — poolcheck's family closure)."""

    def __init__(self, classes: Dict[str, ClassModel]):
        self.classes = classes
        anc: Dict[str, Set[str]] = {}

        def ancestors(name: str, seen: Set[str]) -> Set[str]:
            for b in classes[name].bases if name in classes else ():
                if b in classes and b not in seen:
                    seen.add(b)
                    ancestors(b, seen)
            return seen

        family: Dict[str, Set[str]] = {}
        for name in classes:
            family[name] = {name} | ancestors(name, set())
        for name, fam in family.items():
            for a in list(fam):
                family.setdefault(a, {a}).add(name)
        self._family: Dict[str, Set[str]] = {}
        for name in classes:
            group: Set[str] = set()
            for member in family.get(name, {name}):
                group |= family.get(member, {member})
            self._family[name] = group

    def family(self, name: str) -> Set[str]:
        return self._family.get(name, {name})

    def _members(self, name: str) -> List[ClassModel]:
        return [self.classes[m] for m in sorted(self.family(name))
                if m in self.classes]

    def family_threaded(self, name: str) -> bool:
        return any(cm.threaded for cm in self._members(name))

    def family_owned(self, name: str) -> Set[str]:
        out: Set[str] = set()
        for cm in self._members(name):
            out |= cm.owned
        return out

    def family_lock_attrs(self, name: str) -> Set[str]:
        out: Set[str] = set()
        for cm in self._members(name):
            out |= set(cm.lock_attrs)
            for s in cm.methods.values():
                for r in s.regions:
                    out.add(r.attr)
        return out

    def lock_kind(self, name: str, attr: str) -> str:
        for cm in self._members(name):
            if attr in cm.lock_attrs:
                return cm.lock_attrs[attr]
        return "lock"

    def lock_id(self, name: str, attr: str) -> str:
        """Stable cross-file identity: the family member that assigns
        the lock names it (else the alphabetically-first member)."""
        owners = [cm.name for cm in self._members(name)
                  if attr in cm.lock_attrs]
        owner = sorted(owners)[0] if owners else min(self.family(name))
        return f"{owner}.{attr}"

    def family_methods(self, name: str) -> Dict[str, List[Tuple[ClassModel, MethodSummary]]]:
        out: Dict[str, List[Tuple[ClassModel, MethodSummary]]] = {}
        for cm in self._members(name):
            for mname, s in cm.methods.items():
                out.setdefault(mname, []).append((cm, s))
        return out

    def family_entries(self, name: str) -> Set[str]:
        out: Set[str] = set()
        for cm in self._members(name):
            out |= cm.entry_names
        return out

    def family_guarded(self, name: str) -> Dict[str, Set[str]]:
        """field -> the set of lock ids it is written under, anywhere
        in the family (lifecycle methods excluded)."""
        out: Dict[str, Set[str]] = {}
        for cm in self._members(name):
            for mname, s in cm.methods.items():
                if mname.split(".")[0] in _LIFECYCLE_METHODS:
                    continue
                for acc in s.writes:
                    for attr in acc.held:
                        out.setdefault(acc.field, set()).add(
                            self.lock_id(name, attr))
        return out

    def _reach(self, name: str, starts: Set[str]) -> Set[str]:
        meths = self.family_methods(name)
        seen = set(m for m in starts if m in meths)
        frontier = list(seen)
        while frontier:
            m = frontier.pop()
            for _cm, s in meths.get(m, ()):
                for callee in s.self_calls():
                    if callee in meths and callee not in seen:
                        seen.add(callee)
                        frontier.append(callee)
        return seen

    def contexts(self, name: str) -> Dict[str, str]:
        """method -> 'loop' | 'caller' | 'both' | 'lifecycle' for the
        whole family. Loop = reachable from a Thread entry point;
        caller = reachable from the public surface."""
        meths = self.family_methods(name)
        entries = self.family_entries(name)
        loop = self._reach(name, entries)
        public = {m for m in meths
                  if not m.startswith("_") or m == "__call__"}
        caller = self._reach(name, public)
        out: Dict[str, str] = {}
        for m in meths:
            if m.split(".")[0] in _LIFECYCLE_METHODS:
                out[m] = "lifecycle"
            elif m in loop and m in caller:
                out[m] = "both"
            elif m in loop:
                out[m] = "loop"
            else:
                out[m] = "caller"
        return out


def build_lock_model(units: List[Tuple[str, ast.Module]]) -> LockModel:
    """units = [(rel_path, parsed module)]. Collects every class; later
    files win name collisions (poolcheck's historical flat-dict
    behavior)."""
    classes: Dict[str, ClassModel] = {}
    for rel, tree in units:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                classes[node.name] = ClassModel(rel, node)
    return LockModel(classes)

# ---------------------------------------------------------------------------
# static rules


def _rule_unguarded_writes(model: LockModel, fl_by_rel: Dict[str, _RFileLint]):
    seen_families: Set[frozenset] = set()
    for name in sorted(model.classes):
        fam = frozenset(model.family(name))
        if fam in seen_families:
            continue
        seen_families.add(fam)
        guarded = model.family_guarded(name)
        if not guarded:
            continue
        ctxs = model.contexts(name)
        entries = model.family_entries(name)
        meths = model.family_methods(name)
        # which contexts touch each guarded field (reads or writes)
        touch: Dict[str, Set[str]] = {f: set() for f in guarded}
        for mname, impls in meths.items():
            if ctxs.get(mname) == "lifecycle":
                continue
            for _cm, s in impls:
                for acc in s.reads + s.writes:
                    if acc.field in touch:
                        touch[acc.field].add(ctxs.get(mname, "caller"))
        for mname, impls in meths.items():
            if ctxs.get(mname) == "lifecycle":
                continue
            for cm, s in impls:
                for acc in s.writes:
                    if acc.field not in guarded:
                        continue
                    ids = {model.lock_id(name, a) for a in acc.held}
                    if ids & guarded[acc.field]:
                        continue
                    wctx = ctxs.get(mname, "caller")
                    if entries:
                        others = touch[acc.field] - {wctx}
                        if wctx != "both" and not others:
                            continue  # single-context field: no race
                    locks = ", ".join(sorted(guarded[acc.field]))
                    fl = fl_by_rel.get(cm.rel)
                    if fl is None:
                        continue
                    fl.add(
                        "error", "race-unguarded-write", acc.lineno,
                        f"in {cm.name}.{mname}(): writes "
                        f"self.{acc.field} lock-free, but that field is "
                        f"guarded by {locks} on other paths and is "
                        "reachable from another thread context — take "
                        "the lock, or annotate a deliberate relaxed "
                        "write '# fflint: race-ok (reason)'",
                        s.lineno)


def _lock_order_edges(model: LockModel):
    """(lock_id_from, lock_id_to, rel, lineno, note) edges: lexical
    nesting plus one-level call resolution (a call made while holding a
    lock, to any scanned method that directly acquires another)."""
    # lock ids directly acquired per method name, for name-resolution
    acquires_by_name: Dict[str, List[Tuple[str, str]]] = {}
    for cname, cm in model.classes.items():
        for mname, s in cm.methods.items():
            for r in s.regions:
                acquires_by_name.setdefault(mname, []).append(
                    (model.lock_id(cname, r.attr),
                     model.lock_kind(cname, r.attr)))
    edges: List[Tuple[str, str, str, int, str]] = []
    for cname in sorted(model.classes):
        cm = model.classes[cname]
        for mname, s in cm.methods.items():
            for r in s.regions:
                if r.held_before:
                    to_id = model.lock_id(cname, r.attr)
                    for a in r.held_before:
                        from_id = model.lock_id(cname, a)
                        if from_id != to_id:
                            edges.append((from_id, to_id, cm.rel,
                                          r.lineno,
                                          f"{cname}.{mname} nests "
                                          f"{r.attr} under {a}"))
            for c in s.calls:
                if not c.held:
                    continue
                callee = c.dotted[-1]
                if callee.startswith("__"):
                    continue
                held_ids = {model.lock_id(cname, a) for a in c.held}
                kinds = {model.lock_id(cname, a):
                         model.lock_kind(cname, a) for a in c.held}
                same_object = (len(c.dotted) == 2
                               and c.dotted[0] == "self")
                for to_id, _to_kind in acquires_by_name.get(callee, ()):
                    for from_id in sorted(held_ids):
                        if from_id == to_id \
                                and kinds.get(from_id) == "rlock":
                            continue  # reentrant: not a self-deadlock
                        if from_id == to_id and not same_object:
                            # name-resolved onto a DIFFERENT object (for
                            # example self._inner.submit while holding
                            # our own lock in a same-named method): that
                            # instance's lock is not this lock
                            continue
                        edges.append((from_id, to_id, cm.rel, c.lineno,
                                      f"{cname}.{mname} holds "
                                      f"{from_id} and calls "
                                      f"{'.'.join(c.dotted)} which "
                                      f"acquires {to_id}"))
    return edges


def _rule_lock_order(model: LockModel, fl_by_rel: Dict[str, _RFileLint]):
    edges = _lock_order_edges(model)
    graph: Dict[str, Set[str]] = {}
    witness: Dict[Tuple[str, str], Tuple[str, int, str]] = {}
    for f, t, rel, ln, note in edges:
        graph.setdefault(f, set()).add(t)
        graph.setdefault(t, set())
        witness.setdefault((f, t), (rel, ln, note))
    # Tarjan SCC — a cycle is an SCC of size >1, or a self-edge
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str):
        work = [(v, iter(sorted(graph[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(sorted(graph[w]))))
                    advanced = True
                    break
                elif w in on:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    for scc in sccs:
        cyc = sorted(scc)
        self_loop = len(cyc) == 1 and cyc[0] in graph.get(cyc[0], ())
        if len(cyc) < 2 and not self_loop:
            continue
        ws = sorted((witness[(f, t)], f, t)
                    for f in cyc for t in graph.get(f, ())
                    if t in cyc and (f, t) in witness)
        (rel, ln, _note), _f, _t = ws[0]
        detail = "; ".join(f"{f} -> {t} ({witness[(f, t)][0]}:"
                           f"{witness[(f, t)][1]}, "
                           f"{witness[(f, t)][2]})"
                           for (_w, f, t) in ws)
        fl = fl_by_rel.get(rel)
        if fl is None:
            fl = next(iter(fl_by_rel.values()))
        fl.add(
            "error", "lock-order-cycle", ln,
            f"locks {{{', '.join(cyc)}}} are acquired in conflicting "
            f"orders — a cross-thread deadlock is reachable: {detail}. "
            "Impose one acquisition order (or annotate "
            "'# fflint: race-ok (reason)' at a witness site)")


def _rule_lock_held_blocking(model: LockModel,
                             fl_by_rel: Dict[str, _RFileLint]):
    blocking_methods: Dict[str, List[Tuple[str, str, int]]] = {}
    for cname, cm in model.classes.items():
        for mname, s in cm.methods.items():
            for desc, ln, _held in s.blocking:
                blocking_methods.setdefault(mname, []).append(
                    (cname, desc, ln))
    for cname in sorted(model.classes):
        cm = model.classes[cname]
        fl = fl_by_rel.get(cm.rel)
        if fl is None:
            continue
        for mname, s in cm.methods.items():
            for desc, ln, held in s.blocking:
                fl.add(
                    "warning", "lock-held-device-sync", ln,
                    f"in {cname}.{mname}(): {desc}() blocks while "
                    f"holding {', '.join(sorted(held))} — every other "
                    "thread contending for the lock stalls behind the "
                    "sync (the drain-stall class); move it outside the "
                    "critical section, or annotate "
                    "'# fflint: race-ok (reason)'",
                    s.lineno)
            for c in s.calls:
                if not c.held:
                    continue
                callee = c.dotted[-1]
                if callee.startswith("__") or callee in _BLOCKING:
                    continue
                for ocls, desc, oln in blocking_methods.get(callee, ()):
                    fl.add(
                        "warning", "lock-held-device-sync", c.lineno,
                        f"in {cname}.{mname}(): calls "
                        f"{'.'.join(c.dotted)}() while holding "
                        f"{', '.join(sorted(c.held))}, and "
                        f"{ocls}.{callee}() blocks on {desc}() "
                        f"({ocls}:{oln}) — the lock is held across a "
                        "blocking sync; move the call outside the "
                        "critical section, or annotate "
                        "'# fflint: race-ok (reason)'",
                        s.lineno)
                    break  # one finding per call site


def _region_events(model: LockModel, name: str, cm: ClassModel,
                   s: MethodSummary):
    """Ordered same-lock acquisition events inside one method: direct
    regions, plus calls (lock not held) to same-family methods that
    acquire it. Read/write sets expand same-family calls one level."""
    meths = model.family_methods(name)

    def expand(reads: Set[str], writes: Set[str], calls) -> Tuple[Set[str], Set[str]]:
        r, w = set(reads), set(writes)
        for callee in calls:
            for _cm2, s2 in meths.get(callee, ()):
                r |= {a.field for a in s2.reads}
                w |= {a.field for a in s2.writes}
        return r, w

    events: List[Tuple[str, int, Set[str], Set[str], bool]] = []
    for reg in s.regions:
        r, w = expand(set(reg.reads), set(reg.writes), reg.calls)
        events.append((reg.attr, reg.lineno, r, w, False))
    for c in s.calls:
        if len(c.dotted) != 2 or c.dotted[0] != "self":
            continue
        callee = c.dotted[1]
        if callee == s.name:
            continue
        for _cm2, s2 in meths.get(callee, ()):
            for reg in s2.regions:
                if reg.attr in c.held:
                    continue
                r, w = expand(set(reg.reads), set(reg.writes), reg.calls)
                events.append((reg.attr, c.lineno, r, w, c.in_return))
    events.sort(key=lambda e: e[1])
    return events


def _rule_atomicity_split(model: LockModel,
                          fl_by_rel: Dict[str, _RFileLint]):
    for name in sorted(model.classes):
        cm = model.classes[name]
        fl = fl_by_rel.get(cm.rel)
        if fl is None:
            continue
        guarded = model.family_guarded(name)
        if not guarded:
            continue
        for mname, s in cm.methods.items():
            if mname.split(".")[0] in _LIFECYCLE_METHODS:
                continue
            events = _region_events(model, name, cm, s)
            by_attr: Dict[str, List[Tuple[int, Set[str], Set[str],
                                          bool]]] = {}
            for attr, ln, r, w, term in events:
                by_attr.setdefault(attr, []).append((ln, r, w, term))
            for attr, evs in by_attr.items():
                if len(evs) < 2:
                    continue
                lid = model.lock_id(name, attr)
                fields = {f for f, ids in guarded.items() if lid in ids}
                for i, (ln1, r1, _w1, term1) in enumerate(evs):
                    if term1:
                        continue  # `return call()`: nothing runs after
                    for ln2, _r2, w2, _t2 in evs[i + 1:]:
                        split = sorted(r1 & w2 & fields)
                        if not split:
                            continue
                        fl.add(
                            "warning", "atomicity-split", ln2,
                            f"in {name}.{mname}(): reads "
                            f"self.{split[0]} under {attr} (line {ln1}) "
                            "then releases and re-acquires it to write "
                            "the same field — the check-then-act is not "
                            "atomic; merge into one critical section, "
                            "or annotate '# fflint: race-ok (reason)'",
                            s.lineno)
                        break
                    else:
                        continue
                    break


def _collect_file_lints(paths: List[str],
                        rel_override: Optional[str] = None
                        ) -> List[_RFileLint]:
    files: List[Tuple[str, str]] = []
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in paths:
        if os.path.isdir(p):
            for dirpath, _dirs, names in os.walk(p):
                for fn in sorted(names):
                    if fn.endswith(".py"):
                        full = os.path.join(dirpath, fn)
                        files.append((full, os.path.relpath(full, base)))
        elif os.path.exists(p):
            files.append((p, rel_override or os.path.basename(p)))
    out: List[_RFileLint] = []
    for full, rel in files:
        with open(full) as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=full)
        except SyntaxError as e:
            fl = _RFileLint(rel, "", ast.Module(body=[], type_ignores=[]))
            fl.findings.append(Finding(
                "racecheck", "error", "syntax-error",
                f"{rel}:{e.lineno}", str(e)))
            out.append(fl)
            continue
        out.append(_RFileLint(rel, src, tree))
    return out


def _lint(fls: List[_RFileLint]) -> List[Finding]:
    model = build_lock_model([(fl.rel, fl.tree) for fl in fls])
    fl_by_rel = {fl.rel: fl for fl in fls}
    _rule_unguarded_writes(model, fl_by_rel)
    _rule_lock_order(model, fl_by_rel)
    _rule_lock_held_blocking(model, fl_by_rel)
    _rule_atomicity_split(model, fl_by_rel)
    out: List[Finding] = []
    for fl in fls:
        fl.stale_pragmas()
        out += fl.findings
    out.sort(key=lambda f: f.where)
    return out


def lint_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    return _lint(_collect_file_lints([path], rel_override=rel))


def lint_paths(paths: List[str]) -> List[Finding]:
    return _lint(_collect_file_lints(paths))

# ---------------------------------------------------------------------------
# dynamic arm: bounded interleaving model checking over abstract
# labeled-transition-system models of the three cross-thread protocols

DEFAULT_SWITCH_BOUND = 8

PROTOCOL_INVARIANTS = {
    "single-owner": "a submitted, unfinished request is owned by "
                    "exactly one location (queue, worker slot, handoff "
                    "in-hand) at every instant",
    "future-dropped": "every submitted request's future is resolved — "
                      "never stranded in a detached server or orphaned "
                      "mid-handoff",
    "future-double-resolve": "a request's future is resolved exactly "
                             "once",
    "tier-partition": "a KV payload lives in at most one of {source "
                      "pool, tier, fetcher in-flight, destination "
                      "pool} — the partition holds mid-fetch",
    "payload-conservation": "every payload is accounted for: resident, "
                            "spilled, in flight, fetched, or counted "
                            "dropped (the poolcheck conservation "
                            "mirror)",
    "free-accounting": "free + resident pages equal the pool size on "
                       "both sides of the tier (the poolcheck "
                       "free-accounting mirror)",
    "lru-capacity": "the tier never exceeds its capacity; overflow "
                    "drops the LRU-oldest entry and counts it",
    "swap-during-handoff": "the controller never detaches a server "
                           "while a handoff is in flight on its loop "
                           "thread",
    "dispatch-buffer-owner": "an in-flight launch's token buffer has "
                             "exactly one owner at "
                             "every instant — the device until the "
                             "fence retires it, host bookkeeping only "
                             "after",
    "stale-page-table": "admission beside a dispatch takes only FREE "
                        "pages; no page referenced by an in-flight "
                        "dispatch's table is freed, reassigned or "
                        "moved before its replay lands (a late EOS "
                        "keeps its pages a launch longer; defrag "
                        "fences first)",
    "deadlock": "some thread can always make progress until the "
                "protocol completes",
}


class Action(NamedTuple):
    """One enabled transition: thread id, label, and the shared-state
    footprint the DPOR independence relation is computed from."""

    tid: str
    label: str
    reads: FrozenSet[str]
    writes: FrozenSet[str]


def _independent(a: Action, b: Action) -> bool:
    return (a.tid != b.tid
            and not (a.writes & (b.reads | b.writes))
            and not (b.writes & a.reads))


class ProtocolModel:
    """Base for the abstract protocol LTS models: per-thread program
    counters, enabled() actions with declared footprints, state-scope
    check() plus terminal check_final()/check_stuck()."""

    NAME = "?"

    def __init__(self, mutations: Tuple[str, ...] = ()):
        self.mutations = tuple(mutations)

    def clone(self):
        return copy.deepcopy(self)

    def check(self) -> List[str]:
        return []

    def check_final(self) -> List[str]:
        return []

    def check_stuck(self) -> List[str]:
        return [f"deadlock: no thread can make progress and the "
                f"{self.NAME} protocol has not completed"]


class HandoffModel(ProtocolModel):
    """Protocol 1 — the prefill→decode handoff through the shared tier
    (disagg/workers.py PrefillWorker._on_prefill_complete feeding
    PagedGenerationServer.submit_request): the prefill loop publishes
    the tail, spills the request's pages, frees + clears the slot with
    the request in hand, then enqueues it on the decode side, whose
    admission fetches the payload back out of the tier."""

    NAME = "handoff"
    N = 2

    def __init__(self, mutations: Tuple[str, ...] = ()):
        super().__init__(mutations)
        self.fut = ["pending"] * self.N
        self.resolved_n = [0] * self.N
        self.client_next = 0
        self.prefill_q: List[int] = []
        self.pslot: Optional[List[int]] = None  # [rid, pc]
        self.in_hand: Optional[int] = None
        self.decode_q: List[int] = []
        self.dslot: Optional[List[int]] = None  # [rid, pc]
        self.kv_prefill: Set[int] = set()
        self.tier: Set[int] = set()
        self.kv_decode: Set[int] = set()

    def enabled(self) -> List[Action]:
        acts: List[Action] = []
        if self.client_next < self.N:
            acts.append(Action("client", f"submit({self.client_next})",
                               frozenset(), frozenset({"prefill_q"})))
        # the prefill loop is one sequential thread: enqueue the request
        # in hand, else advance the slot, else take the next submission
        if self.in_hand is not None:
            acts.append(Action("prefill", f"enqueue({self.in_hand})",
                               frozenset(),
                               frozenset({"decode_q", "in_hand"})))
        elif self.pslot is not None:
            r, pc = self.pslot
            step = [("compute", frozenset(),
                     frozenset({"pslot", f"kv{r}"})),
                    ("publish_tail", frozenset(), frozenset({"pslot"})),
                    ("spill", frozenset({f"kv{r}"}),
                     frozenset({"pslot", "tier", f"kv{r}"})),
                    ("free_clear", frozenset(),
                     frozenset({"pslot", "in_hand"}))][pc]
            acts.append(Action("prefill", f"{step[0]}({r})",
                               step[1], step[2]))
        elif self.prefill_q:
            acts.append(Action("prefill", "take",
                               frozenset({"prefill_q"}),
                               frozenset({"prefill_q", "pslot"})))
        if self.dslot is None:
            if self.decode_q:
                acts.append(Action("decode", "take",
                                   frozenset({"decode_q"}),
                                   frozenset({"decode_q", "dslot"})))
        else:
            r, pc = self.dslot
            if pc == 0:
                acts.append(Action("decode", f"fetch({r})",
                                   frozenset({"tier"}),
                                   frozenset({"tier", "dslot",
                                              f"kv{r}"})))
            else:
                acts.append(Action("decode", f"finish({r})",
                                   frozenset(),
                                   frozenset({f"fut{r}", "dslot",
                                              f"kv{r}"})))
        return acts

    def apply(self, action: Action):
        lbl = action.label
        op = lbl.split("(")[0]
        arg = int(lbl[:-1].split("(")[1]) if "(" in lbl else None
        if op == "submit":
            self.prefill_q.append(arg)
            self.client_next += 1
        elif op == "take" and action.tid == "prefill":
            self.pslot = [self.prefill_q.pop(0), 0]
        elif op == "compute":
            self.kv_prefill.add(arg)
            self.pslot[1] = 1
        elif op == "publish_tail":
            self.pslot[1] = 2
        elif op == "spill":
            self.kv_prefill.discard(arg)
            self.tier.add(arg)
            self.pslot[1] = 3
        elif op == "free_clear":
            self.in_hand = self.pslot[0]
            self.pslot = None
        elif op == "enqueue":
            self.decode_q.append(arg)
            if "double_submit" in self.mutations:
                # SEEDED DEFECT: the handoff retries after a spurious
                # error and submits the SAME request object twice — two
                # decode-side owners now share one future
                self.decode_q.append(arg)
            self.in_hand = None
        elif op == "take":
            self.dslot = [self.decode_q.pop(0), 0]
        elif op == "fetch":
            self.tier.discard(arg)
            self.kv_decode.add(arg)
            self.dslot[1] = 1
        elif op == "finish":
            self.kv_decode.discard(arg)
            self.resolved_n[arg] += 1
            self.fut[arg] = "resolved"
            self.dslot = None

    def _owners(self, r: int) -> int:
        n = self.prefill_q.count(r) + self.decode_q.count(r)
        if self.pslot is not None and self.pslot[0] == r:
            n += 1
        if self.in_hand == r:
            n += 1
        if self.dslot is not None and self.dslot[0] == r:
            n += 1
        return n

    def check(self) -> List[str]:
        v: List[str] = []
        for r in range(self.N):
            own = self._owners(r)
            if self.fut[r] == "resolved":
                if own:
                    v.append(f"single-owner: finished request {r} is "
                             f"still owned by {own} location(s)")
                if self.resolved_n[r] > 1:
                    v.append(f"future-double-resolve: request {r} "
                             f"resolved {self.resolved_n[r]} times")
            elif r < self.client_next and own != 1:
                v.append(f"single-owner: request {r} is owned by {own} "
                         "locations (queues/slots/handoff) — must be "
                         "exactly one")
            places = sum((r in self.kv_prefill, r in self.tier,
                          r in self.kv_decode))
            if places > 1:
                v.append(f"tier-partition: request {r}'s KV is present "
                         f"in {places} locations at once")
        return v

    def done(self) -> bool:
        return (self.client_next == self.N and not self.prefill_q
                and not self.decode_q and self.pslot is None
                and self.dslot is None and self.in_hand is None)

    def check_final(self) -> List[str]:
        v = [f"future-dropped: request {r}'s future is still pending "
             "at protocol completion"
             for r in range(self.N) if self.fut[r] != "resolved"]
        if self.tier:
            v.append("payload-conservation: the tier holds orphan "
                     f"payloads {sorted(self.tier)} at completion")
        return v

    def key(self) -> tuple:
        return (self.client_next, tuple(self.prefill_q),
                tuple(self.pslot or ()), self.in_hand,
                tuple(self.decode_q), tuple(self.dslot or ()),
                tuple(self.fut), tuple(self.resolved_n),
                tuple(sorted(self.kv_prefill)),
                tuple(sorted(self.tier)),
                tuple(sorted(self.kv_decode)))


class TierPoolModel(ProtocolModel):
    """Protocol 2 — concurrent spill/fetch/admission on a pool pair
    through one capacity-bounded LRU tier (disagg/host_tier.py +
    paged/pool.py spill_oldest/prefetch/_fetch_full): the spiller
    thread moves pages out of the prefill pool under pressure while
    the fetcher pops payloads mid-flight into the decode pool; fetch
    is deliberately two steps (pop, then commit) so the mid-fetch
    partition is a checked state, not an argument."""

    NAME = "tierpool"
    HASHES = ("h0", "h1", "h2")
    FETCHES = ("h0", "h2")
    TIER_CAP = 2
    POOL_D = 2

    def __init__(self, mutations: Tuple[str, ...] = ()):
        super().__init__(mutations)
        self.pool_p = list(self.HASHES)
        self.free_p = 0
        self.tier: List[str] = []      # LRU order, oldest first
        self.dropped: List[str] = []
        self.pool_d: List[str] = []
        self.free_d = self.POOL_D
        self.in_flight: Optional[str] = None
        self.spill_i = 0
        self.fetch_i = 0
        self.fetch_pc = 0              # 0 = lookup/pop, 1 = commit
        self.misses = 0

    def enabled(self) -> List[Action]:
        acts: List[Action] = []
        if self.spill_i < len(self.HASHES):
            h = self.HASHES[self.spill_i]
            acts.append(Action("spiller", f"spill({h})",
                               frozenset({"pool_p", "tier"}),
                               frozenset({"pool_p", "tier", "dropped"})))
        if self.fetch_i < len(self.FETCHES):
            h = self.FETCHES[self.fetch_i]
            if self.fetch_pc == 1:
                acts.append(Action("fetcher", f"commit({h})",
                                   frozenset({"in_flight"}),
                                   frozenset({"in_flight", "pool_d"})))
            elif h in self.tier:
                acts.append(Action("fetcher", f"lookup({h})",
                                   frozenset({"tier"}),
                                   frozenset({"tier", "in_flight"})))
            elif h in self.dropped:
                acts.append(Action("fetcher", f"miss({h})",
                                   frozenset({"tier", "dropped"}),
                                   frozenset({"misses"})))
            # else: still resident on the prefill side — the fetcher
            # blocks until the spiller moves it (or drops it)
        return acts

    def apply(self, action: Action):
        op = action.label.split("(")[0]
        h = action.label[:-1].split("(")[1]
        if op == "spill":
            self.pool_p.remove(h)
            self.free_p += 1
            self.tier.append(h)
            if len(self.tier) > self.TIER_CAP:
                self.dropped.append(self.tier.pop(0))  # LRU drop
            self.spill_i += 1
        elif op == "lookup":
            if "fetch_no_remove" not in self.mutations:
                self.tier.remove(h)
            # SEEDED DEFECT (fetch_no_remove): the fetch COPIES the
            # payload instead of moving it — resident ⊎ spilled breaks
            # the instant the commit lands
            self.in_flight = h
            self.fetch_pc = 1
        elif op == "miss":
            self.misses += 1
            self.fetch_i += 1
        elif op == "commit":
            self.pool_d.append(self.in_flight)
            self.free_d -= 1
            self.in_flight = None
            self.fetch_pc = 0
            self.fetch_i += 1

    def check(self) -> List[str]:
        v: List[str] = []
        for h in self.HASHES:
            places = sum((h in self.pool_p, h in self.tier,
                          h == self.in_flight, h in self.pool_d))
            if places > 1:
                v.append(f"tier-partition: payload {h} is in {places} "
                         "of {prefill pool, tier, in-flight, decode "
                         "pool} at once — the mid-fetch partition is "
                         "broken")
            elif places + (1 if h in self.dropped else 0) != 1:
                v.append(f"payload-conservation: payload {h} is in no "
                         "location and was never counted dropped")
        if self.free_p + len(self.pool_p) != len(self.HASHES):
            v.append(f"free-accounting: prefill pool free={self.free_p}"
                     f" + resident={len(self.pool_p)} != "
                     f"{len(self.HASHES)}")
        if self.free_d + len(self.pool_d) != self.POOL_D:
            v.append(f"free-accounting: decode pool free={self.free_d} "
                     f"+ resident={len(self.pool_d)} != {self.POOL_D}")
        if len(self.tier) > self.TIER_CAP:
            v.append(f"lru-capacity: tier holds {len(self.tier)} "
                     f"payloads over capacity {self.TIER_CAP}")
        return v

    def done(self) -> bool:
        return (self.spill_i == len(self.HASHES)
                and self.fetch_i == len(self.FETCHES))

    def check_final(self) -> List[str]:
        if self.misses + len(self.pool_d) != len(self.FETCHES):
            return ["payload-conservation: fetches + misses do not "
                    f"cover the fetch script ({len(self.pool_d)} "
                    f"fetched, {self.misses} missed, "
                    f"{len(self.FETCHES)} attempted)"]
        return []

    def key(self) -> tuple:
        return (tuple(self.pool_p), self.free_p, tuple(self.tier),
                tuple(self.dropped), tuple(self.pool_d), self.free_d,
                self.in_flight, self.spill_i, self.fetch_i,
                self.fetch_pc, self.misses)


class SwapModel(ProtocolModel):
    """Protocol 3 — autopilot drain-and-swap under live submits
    (serving_autopilot.py swap_to vs submit, both under `_swap_lock`;
    serving.py detach_for_swap): the controller warms the successor,
    takes the lock, stops the old loop, joins it at a safe point,
    collects + absorbs the carried queue, starts the successor and
    cuts `inner` over — while a client submits through the same lock
    and a worker thread serves whichever server is running."""

    NAME = "swap"
    N = 2
    SCRIPT = ("warm", "acq", "stop_old", "join", "collect", "absorb",
              "start_new", "cutover", "rel")

    def __init__(self, mutations: Tuple[str, ...] = ()):
        super().__init__(mutations)
        self.holder: Optional[str] = None
        self.inner = "old"
        self.q: Dict[str, List[int]] = {"old": [], "new": []}
        self.running = {"old": True, "new": False}
        self.carried: List[int] = []
        self.collected = False
        self.joined_dirty: Optional[int] = None
        self.fut = ["pending"] * self.N
        self.resolved_n = [0] * self.N
        self.client_i = 0
        self.client_pc = 0             # 0 = acq, 1 = enq, 2 = rel
        self.ctrl_pc = 0
        self.in_hand: Optional[Tuple[str, int]] = None

    def enabled(self) -> List[Action]:
        acts: List[Action] = []
        if self.client_i < self.N:
            if "unlocked_submit" in self.mutations:
                # SEEDED DEFECT: submit skips the swap lock entirely —
                # it can land in the old server inside the detach window
                acts.append(Action(
                    "client", f"enq_unlocked({self.client_i})",
                    frozenset({"inner"}),
                    frozenset({f"q_{self.inner}"})))
            elif self.client_pc == 0:
                if self.holder is None:
                    acts.append(Action("client", "acq",
                                       frozenset({"L"}),
                                       frozenset({"L"})))
            elif self.client_pc == 1:
                acts.append(Action("client", f"enq({self.client_i})",
                                   frozenset({"inner"}),
                                   frozenset({f"q_{self.inner}"})))
            else:
                acts.append(Action("client", "rel", frozenset(),
                                   frozenset({"L"})))
        if self.ctrl_pc < len(self.SCRIPT):
            step = self.SCRIPT[self.ctrl_pc]
            if step == "warm":
                acts.append(Action("controller", "warm", frozenset(),
                                   frozenset({"warmed"})))
            elif step == "acq":
                if self.holder is None:
                    acts.append(Action("controller", "acq",
                                       frozenset({"L"}),
                                       frozenset({"L"})))
            elif step == "stop_old":
                acts.append(Action("controller", "stop_old",
                                   frozenset(),
                                   frozenset({"run_old"})))
            elif step == "join":
                if self.in_hand is None \
                        or "no_safepoint_join" in self.mutations:
                    # SEEDED DEFECT (no_safepoint_join): detach without
                    # waiting for the loop's safe point — a request
                    # mid-handoff on the loop thread is left orphaned
                    acts.append(Action("controller", "join",
                                       frozenset({"in_hand"}),
                                       frozenset({"joined"})))
            elif step == "collect":
                acts.append(Action("controller", "collect",
                                   frozenset({"q_old"}),
                                   frozenset({"q_old", "carried"})))
            elif step == "absorb":
                acts.append(Action("controller", "absorb",
                                   frozenset({"carried"}),
                                   frozenset({"q_new", "carried"})))
            elif step == "start_new":
                acts.append(Action("controller", "start_new",
                                   frozenset(),
                                   frozenset({"run_new"})))
            elif step == "cutover":
                acts.append(Action("controller", "cutover",
                                   frozenset(), frozenset({"inner"})))
            else:
                acts.append(Action("controller", "rel", frozenset(),
                                   frozenset({"L"})))
        if self.in_hand is not None:
            acts.append(Action("worker", f"resolve({self.in_hand[1]})",
                               frozenset({"in_hand"}),
                               frozenset({"fut", "in_hand"})))
        else:
            for s in ("old", "new"):
                if self.running[s] and self.q[s]:
                    acts.append(Action("worker", f"pop({s})",
                                       frozenset({f"q_{s}",
                                                  f"run_{s}"}),
                                       frozenset({f"q_{s}",
                                                  "in_hand"})))
        return acts

    def apply(self, action: Action):
        lbl, tid = action.label, action.tid
        op = lbl.split("(")[0]
        if tid == "client":
            if op == "acq":
                self.holder = "client"
                self.client_pc = 1
            elif op in ("enq", "enq_unlocked"):
                self.q[self.inner].append(self.client_i)
                if op == "enq_unlocked":
                    self.client_i += 1
                else:
                    self.client_pc = 2
            else:
                self.holder = None
                self.client_pc = 0
                self.client_i += 1
        elif tid == "controller":
            if op == "acq":
                self.holder = "controller"
            elif op == "stop_old":
                self.running["old"] = False
            elif op == "join":
                if self.in_hand is not None:
                    self.joined_dirty = self.in_hand[1]
            elif op == "collect":
                self.carried = list(self.q["old"])
                self.q["old"] = []
                self.collected = True
            elif op == "absorb":
                self.q["new"].extend(self.carried)
                self.carried = []
            elif op == "start_new":
                self.running["new"] = True
            elif op == "cutover":
                self.inner = "new"
            elif op == "rel":
                self.holder = None
            self.ctrl_pc += 1
        else:
            if op == "pop":
                s = lbl[:-1].split("(")[1]
                self.in_hand = (s, self.q[s].pop(0))
            else:
                r = int(lbl[:-1].split("(")[1])
                self.resolved_n[r] += 1
                self.fut[r] = "resolved"
                self.in_hand = None

    def check(self) -> List[str]:
        v: List[str] = []
        if self.collected and self.q["old"] \
                and not self.running["old"]:
            v.append("future-dropped: request(s) "
                     f"{self.q['old']} enqueued into the detached old "
                     "server after its queue was collected — the "
                     "submit bypassed the swap lock and the future "
                     "can never resolve")
        if self.joined_dirty is not None:
            v.append("swap-during-handoff: the old server was "
                     f"detached while request {self.joined_dirty} was "
                     "mid-handoff on its loop thread")
        for r in range(self.N):
            own = (self.q["old"].count(r) + self.q["new"].count(r)
                   + self.carried.count(r)
                   + (1 if self.in_hand is not None
                      and self.in_hand[1] == r else 0))
            if self.fut[r] == "resolved":
                if self.resolved_n[r] > 1:
                    v.append(f"future-double-resolve: request {r} "
                             f"resolved {self.resolved_n[r]} times")
                if own:
                    v.append(f"single-owner: finished request {r} is "
                             f"still owned by {own} location(s)")
            elif r < self.client_i and own != 1:
                v.append(f"single-owner: request {r} is owned by {own} "
                         "locations — must be exactly one")
        return v

    def done(self) -> bool:
        return (self.ctrl_pc == len(self.SCRIPT)
                and self.client_i == self.N and self.in_hand is None
                and not self.q["old"] and not self.q["new"]
                and not self.carried)

    def check_final(self) -> List[str]:
        return [f"future-dropped: request {r}'s future is still "
                "pending at protocol completion"
                for r in range(self.N) if self.fut[r] != "resolved"]

    def key(self) -> tuple:
        return (self.holder, self.inner, tuple(self.q["old"]),
                tuple(self.q["new"]), tuple(self.running.items()),
                tuple(self.carried), self.collected, self.joined_dirty,
                tuple(self.fut), tuple(self.resolved_n), self.client_i,
                self.client_pc, self.ctrl_pc, self.in_hand)


class LaunchAheadModel(ProtocolModel):
    """Protocol 4 — the one-deep launch pipeline of the serving loop
    (paged/scheduler.py `_launch` / `_retire`): the host dispatches
    launch k + 1 BEFORE it takes launch k's picks, so the dispatch's
    two invariants hold a launch deep. The token buffer of a
    launch is the device's until the host's fetch retires it (`_retire`
    waits; nothing reads a pick early), and A PAGE NAMED BY A LAUNCH IN
    FLIGHT IS NEITHER FREED NOR MOVED: a request that stops on an EOS in
    launch k already has a row in launch k + 1, so its page goes back
    only when k + 1 is taken, and a defrag first fences. Admission runs
    beside a launch in flight: it takes free pages only.

    The scenario: request A holds page 0 and emits its EOS in launch 0;
    request B is submitted at any time and admitted beside a launch with
    a free page; a defrag is asked for before launch 2 (the host fences,
    then compacts: page ids change)."""

    NAME = "launch_ahead"
    N = 3  # launches

    def __init__(self, mutations: Tuple[str, ...] = ()):
        super().__init__(mutations)
        self.launched = 0          # launches dispatched
        self.computed = 0          # launches the device has run
        self.taken = 0             # launches whose picks the host took
        self.host_pc = 0           # 0 dispatch, 1 admit beside, 2 retire
        self.submitted = 0
        self.pending = 0
        self.free: List[int] = [10]
        self.pages: Dict[str, List[int]] = {"A": [0]}
        self.stopped: Set[str] = set()   # the host knows of the EOS
        self.named: List[Tuple[int, ...]] = []   # pages a launch names
        self.rows: List[Tuple[str, ...]] = []    # requests it has rows of
        self.defragged = False
        self.bad_read = False

    def _held(self) -> Set[int]:
        return {p for ps in self.pages.values() for p in ps}

    def _take_enabled(self) -> bool:
        # SEEDED DEFECT (read_before_fence): the host reads a launch's
        # picks without waiting for the device to have run it
        return (self.computed > self.taken
                or "read_before_fence" in self.mutations)

    def enabled(self) -> List[Action]:
        acts: List[Action] = []
        if self.submitted < 1:
            acts.append(Action("client", "submit", frozenset(),
                               frozenset({"pending"})))
        if self.computed < self.launched:   # the device runs in order
            acts.append(Action("device", f"compute({self.computed})",
                               frozenset({"pages"}),
                               frozenset({"buf"})))
        if self.launched < self.N or self.taken < self.launched:
            if self.host_pc == 0 and self.launched < self.N:
                wants_defrag = self.launched == 2 and not self.defragged
                # SEEDED DEFECT (defrag_without_fence): pages move under
                # the launch in flight
                fenced = (self.taken == self.launched
                          or "defrag_without_fence" in self.mutations)
                if wants_defrag and not fenced:
                    if self._take_enabled():
                        acts.append(Action(
                            "host", f"fence_take({self.taken})",
                            frozenset({"buf"}),
                            frozenset({"buf", "pages", "free"})))
                elif wants_defrag:
                    acts.append(Action("host", "defrag",
                                       frozenset({"pages"}),
                                       frozenset({"pages", "free"})))
                else:
                    acts.append(Action("host",
                                       f"dispatch({self.launched})",
                                       frozenset({"pages"}),
                                       frozenset({"buf"})))
            elif self.host_pc == 1:
                acts.append(Action("host", "admit_beside",
                                   frozenset({"pending", "free"}),
                                   frozenset({"pending", "free", "pages"})))
            elif self.host_pc == 2 or self.launched == self.N:
                # the pipeline's own step takes every launch but the one
                # just dispatched; the last pass takes them all
                upto = self.launched if self.launched == self.N \
                    and self.host_pc == 0 else self.launched - 1
                if self.taken < upto:
                    if self._take_enabled():
                        acts.append(Action("host", f"take({self.taken})",
                                           frozenset({"buf"}),
                                           frozenset({"buf", "pages",
                                                      "free"})))
                else:
                    acts.append(Action("host", "next", frozenset(),
                                       frozenset()))
        return acts

    def _take(self):
        j = self.taken
        if self.computed <= j:
            self.bad_read = True
        self.taken += 1
        if j == 0:
            self.stopped.add("A")       # launch 0 picked A's EOS
        in_flight = {r for rows in self.rows[self.taken:self.launched]
                     for r in rows}
        for r in sorted(self.stopped):
            # SEEDED DEFECT (free_at_late_stop): the pages go back when
            # the host learns of the stop, under the row in flight
            if r in self.pages and (
                    r not in in_flight
                    or "free_at_late_stop" in self.mutations):
                self.free += self.pages.pop(r)

    def apply(self, action: Action):
        op = action.label.split("(")[0]
        if op == "submit":
            self.pending += 1
            self.submitted += 1
        elif op == "compute":
            self.computed += 1
        elif op == "dispatch":
            rows = tuple(r for r in sorted(self.pages)
                         if r not in self.stopped)
            self.rows.append(rows)
            self.named.append(tuple(p for r in rows
                                    for p in self.pages[r]))
            self.launched += 1
            self.host_pc = 1
        elif op == "admit_beside":
            if self.pending and self.free:
                self.pages["B"] = [self.free.pop()]
                self.pending -= 1
            self.host_pc = 2
        elif op in ("take", "fence_take"):
            self._take()
        elif op == "next":
            self.host_pc = 0
        elif op == "defrag":
            # compaction: every held page gets a new id
            self.pages = {r: [p + 100 for p in ps]
                          for r, ps in self.pages.items()}
            self.defragged = True

    def check(self) -> List[str]:
        v: List[str] = []
        if self.bad_read:
            v.append("dispatch-buffer-owner: the host took a launch's "
                     "picks while the device still owned them — the "
                     "fetch did not wait for the launch")
        for j in range(self.taken, self.launched):
            gone = set(self.named[j]) - self._held()
            if gone:
                v.append("stale-page-table: page(s) "
                         f"{sorted(gone)} named by launch {j}, still in "
                         "flight, were freed or moved before its picks "
                         "were taken")
                break
        return v

    def done(self) -> bool:
        return (self.launched == self.N and self.taken == self.N
                and self.submitted == 1)

    def check_final(self) -> List[str]:
        total = len(self.free) + len(self._held())
        if total != 2:
            return ["free-accounting: free + held pages number "
                    f"{total}, pool holds 2"]
        if "A" in self.pages:
            return ["free-accounting: request A stopped and still holds "
                    "its page after every launch was taken"]
        return []

    def key(self) -> tuple:
        return (self.launched, self.computed, self.taken, self.host_pc,
                self.submitted, self.pending, tuple(self.free),
                tuple(sorted((r, tuple(p)) for r, p in self.pages.items())),
                tuple(sorted(self.stopped)), tuple(self.named),
                self.defragged, self.bad_read)


PROTOCOLS = {m.NAME: m for m in
             (HandoffModel, TierPoolModel, SwapModel,
              LaunchAheadModel)}


class InterleaveResult:
    """Outcome of one bounded interleaving exploration."""

    def __init__(self, model: str, explored: int, distinct: int,
                 hits: List[Tuple[str, str, Tuple[str, ...]]],
                 truncated: bool, bound: int):
        self.model = model
        self.explored = explored
        self.distinct = distinct
        self.hits = hits            # (invariant, detail, minimal trace)
        self.truncated = truncated
        self.bound = bound


def explore_interleavings(factory, max_switches: int = DEFAULT_SWITCH_BOUND,
                          max_states: int = 500_000,
                          max_findings: int = 4,
                          prune: bool = True) -> InterleaveResult:
    """BFS over every thread interleaving of the model up to
    `max_switches` context switches, with sleep-set pruning (disable
    via prune=False — tests assert the distinct-state set is identical
    either way, the soundness cross-check). check() runs on every
    generated state BEFORE dedup, so no violation is pruned away; the
    first trace reaching each invariant is minimal by BFS order."""
    root = factory()
    hits: List[Tuple[str, str, Tuple[str, ...]]] = []

    def record(found: List[str], trace: Tuple[str, ...]):
        for msg in found:
            name = msg.split(":", 1)[0]
            if all(h[0] != name for h in hits):
                hits.append((name, msg, trace))

    record(root.check(), ())
    frontier: deque = deque([(root, (), None, 0, frozenset())])
    visited: Dict[tuple, List[Tuple[int, FrozenSet[Action]]]] = {}
    distinct: Set[tuple] = {root.key()}
    explored = 0
    while frontier and len(hits) < max_findings \
            and explored < max_states:
        state, trace, last, sw, sleep = frontier.popleft()
        explored += 1
        acts = state.enabled()
        if not acts:
            if state.done():
                record(state.check_final(), trace)
            else:
                record(state.check_stuck(), trace)
            continue
        local_done: List[Action] = []
        for a in acts:
            if prune and a in sleep:
                continue
            nsw = sw + (1 if last is not None and a.tid != last else 0)
            if nsw > max_switches:
                continue
            child = state.clone()
            child.apply(a)
            ctrace = trace + (f"{a.tid}:{a.label}",)
            found = child.check()
            if found:
                record(found, ctrace)
                local_done.append(a)
                continue  # a broken state's successors prove nothing
            child_sleep = frozenset(
                b for b in (set(sleep) | set(local_done))
                if _independent(a, b)) if prune else frozenset()
            k = (child.key(), a.tid)
            dom = visited.get(k)
            if dom is not None and any(
                    psw <= nsw and pset <= child_sleep
                    for psw, pset in dom):
                local_done.append(a)
                continue
            visited.setdefault(k, []).append((nsw, child_sleep))
            distinct.add(child.key())
            frontier.append((child, ctrace, a.tid, nsw, child_sleep))
            local_done.append(a)
    return InterleaveResult(
        root.NAME, explored, len(distinct), hits,
        truncated=bool(frontier) and explored >= max_states,
        bound=max_switches)


def replay_interleaving(factory, trace) -> List[str]:
    """Re-execute a counterexample interleaving from the initial state
    and return every violation it produces (empty = does not
    reproduce). Each step is 'tid:label' as emitted in traces."""
    state = factory()
    out: List[str] = list(state.check())
    for step in trace:
        tid, label = step.split(":", 1)
        match = [a for a in state.enabled()
                 if a.tid == tid and a.label == label]
        if not match:
            out.append(f"replay-diverged: {step} not enabled")
            return out
        state.apply(match[0])
        out += state.check()
    if not state.enabled():
        out += state.check_final() if state.done() \
            else state.check_stuck()
    return out

# ---------------------------------------------------------------------------
# pass registration


def _interleaving_findings(ctx) -> List[Finding]:
    findings: List[Finding] = []
    mutations = tuple(getattr(ctx, "racecheck_mutations", ()) or ())
    bound = getattr(ctx, "racecheck_switch_bound", None) \
        or DEFAULT_SWITCH_BOUND
    trace_dir = getattr(ctx, "racecheck_trace_dir", None)
    summary: Dict[str, object] = {"switch_bound": bound, "models": {}}
    total_explored = 0
    total_distinct = 0
    for name in sorted(PROTOCOLS):
        model_cls = PROTOCOLS[name]
        res = explore_interleavings(
            lambda cls=model_cls: cls(mutations=mutations),
            max_switches=bound)
        total_explored += res.explored
        total_distinct += res.distinct
        summary["models"][name] = {
            "explored": res.explored,
            "distinct_states": res.distinct,
            "violations": len(res.hits),
            "truncated": res.truncated,
        }
        for inv, detail, trace in res.hits:
            spec = PROTOCOL_INVARIANTS.get(inv, detail)
            findings.append(Finding(
                "racecheck", "error", f"ilv-{inv}",
                f"racecheck:model/{name}",
                f"protocol invariant violated in the {name} model "
                f"under mutations {list(mutations)}: {detail}. "
                f"Invariant: {spec}. Minimal interleaving "
                f"({len(trace)} steps, switch bound {bound}): "
                + " -> ".join(trace)))
            if trace_dir:
                os.makedirs(trace_dir, exist_ok=True)
                path = os.path.join(
                    trace_dir, f"interleave-{name}-{inv}.json")
                with open(path, "w") as f:
                    json.dump({"model": name, "invariant": inv,
                               "mutations": list(mutations),
                               "switch_bound": bound,
                               "detail": detail,
                               "trace": list(trace),
                               "replay": ("flexflow_tpu.analysis."
                                          "racecheck."
                                          "replay_interleaving")},
                              f, indent=2)
        if res.truncated:
            findings.append(Finding(
                "racecheck", "warning", "ilv-truncated",
                f"racecheck:model/{name}",
                f"exploration of the {name} model was truncated at "
                f"{res.explored} states before exhausting switch "
                f"bound {bound} — coverage is partial"))
    summary["explored"] = total_explored
    summary["distinct_states"] = total_distinct
    ctx.racecheck_summary = summary
    findings.append(Finding(
        "racecheck", "info", "interleavings-explored",
        "racecheck:model",
        f"explored {total_explored} states "
        f"({total_distinct} distinct) across {len(PROTOCOLS)} "
        f"protocol models at context-switch bound {bound}; "
        f"{len(PROTOCOL_INVARIANTS)} invariant kinds asserted at "
        "every state"))
    return findings


@register_pass("racecheck")
def racecheck_pass(ctx) -> List[Finding]:
    paths = getattr(ctx, "racecheck_paths", None) or \
        default_lint_paths()
    findings = lint_paths(paths)
    n_err = sum(1 for f in findings if f.severity == "error")
    findings.append(Finding(
        "racecheck", "info", "lock-lint-summary", "racecheck:lint",
        f"lock-discipline lint over {len(RACE_ROOTS)} roots: "
        f"{len(findings)} finding(s), {n_err} error(s)"))
    if not getattr(ctx, "racecheck_lint_only", False):
        findings += _interleaving_findings(ctx)
    return findings
