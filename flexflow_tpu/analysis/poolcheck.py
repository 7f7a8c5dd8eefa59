"""poolcheck — explicit-state model checking + aliasing lints for the
paged serving state machine (the fifth fflint pass).

The prefix-cache PR made `PagePool` the correctness keystone of the
serving stack: refcounted content-addressed pages, COW tails, an LRU
dead list, leaf-first frees, and a defrag that rewrites every owner's
table. This pass checks that state machine two ways, both driven by the
declarative catalog in analysis/pool_invariants.py:

  MODEL CHECKER — BFS over every reachable configuration of a bounded
      serving scenario (≤3 requests, ≤8 pages, ≤2-page prompts, 2-token
      pages), driving the REAL PagePool through a harness that mirrors
      the scheduler's host-side bookkeeping ops: admission with prefix
      lookup + COW clone + the transient-shortfall rollback, chunked
      prefill with per-block publication, decode with page growth and
      preemption, leaf-first release with tail publication, defrag with
      the owner-table rewrite, and speculative verify/commit with tree
      scratch rows. Every invariant is asserted at every reached state;
      a violation is reported as an `inv-<name>` error finding carrying
      the MINIMAL counterexample trace (BFS order guarantees
      minimality), replayable via `replay()`.

  LINT ARM — an AST pass over serving.py, paged/, spec/ that flags
      write-after-share hazards:

  page-write-outside-cow        (error)   `.at[...].set/.add` on cache
      buffers in a host-side state-machine file (paged/scheduler.py,
      paged/pool.py, spec/server.py) outside the COW clone helper —
      in-place mutation of pool pages bypasses refcount discipline.
  table-write-outside-admission (error)   `self._tables` mutated
      outside the admission/defrag/release lifecycle methods.
  pool-private-access           (warning) `pool._x` underscore-state
      touched outside paged/pool.py — bookkeeping must go through the
      pool's methods or the invariants cannot be maintained.
  unlocked-cross-thread-read    (warning) in a thread-owning server
      class, a PUBLIC method reads a field the scheduler-loop thread
      mutates (or reads pool state) without holding `self._lock`.
      Intentional relaxed reads (metrics snapshots) are annotated
      `# fflint: lock-ok (reason)` on the line or its `def` line.
  stale-pragma                  (info)    a poolcheck directive
      (lock-ok / cow-ok / table-ok / pool-ok) that no longer
      suppresses anything.

CLI: tools/fflint.py runs poolcheck by default (tier-1 gates on it via
tests/test_analysis.py); `--since REV` runs the lint arm only. See
docs/analysis.md (pass, severities, pragmas) and docs/paged.md (the
invariant catalog this pass executes).
"""

from __future__ import annotations

import ast
import copy
import io
import json
import os
import tokenize
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

from flexflow_tpu.analysis import AnalysisContext, Finding, register_pass
from flexflow_tpu.analysis import pool_invariants as inv
from flexflow_tpu.paged.pool import EMPTY_HASH, PagePool

# ---------------------------------------------------------------------------
# model checker: a harness mirroring the scheduler's host-side bookkeeping


class _Req:
    """Model-side request: the subset of _GenRequest state the pool
    bookkeeping depends on."""

    __slots__ = ("prompt", "max_new", "tokens", "state", "pages", "pos",
                 "prefill_pos", "prefill_target", "hashed_blocks")

    def __init__(self, prompt: Tuple[int, ...], max_new: int):
        self.prompt = tuple(int(t) for t in prompt)
        self.max_new = int(max_new)
        self.tokens: List[int] = []
        self.state = "queued"  # queued | active | done
        self.pages: List[int] = []
        self.pos = 0
        self.prefill_pos = 0
        self.prefill_target = 0
        self.hashed_blocks = 0


# bounded scenarios (the ISSUE-9 bounds: ≤3 requests, ≤8 pages, ≤2-page
# prompts). Prompts are crafted to reach every sharing shape: identical
# prompts (page-aligned full-prompt hit → the COW clamp), a prompt
# extension (full-block share + partial-tail COW), and enough decode
# budget to cross page boundaries (decode-time publication + growth).
CONFIGS: Dict[str, Dict] = {
    "base": dict(num_pages=8, page_size=2, slots=2, spec_nodes=0,
                 prompts=((1, 2, 3), (1, 2, 3), (1, 2, 3, 4)),
                 max_new=(2, 1, 1)),
    "spec": dict(num_pages=8, page_size=2, slots=2, spec_nodes=2,
                 prompts=((1, 2, 3), (1, 2, 3)),
                 max_new=(2, 2)),
    # host-memory tier (disagg): a pool SMALL enough that admission
    # pressure must evict-and-spill, a tier small enough to exercise its
    # own capacity drops, and a shared prefix so fetches re-attach.
    # Gains ops: spill (proactive spill_oldest), fetch (prefetch of a
    # spilled hash), adopt (the prefill->decode handoff of a
    # prefill-complete request through the tier).
    "tiered": dict(num_pages=6, page_size=2, slots=2, spec_nodes=0,
                   prompts=((1, 2, 3), (1, 2, 3, 4)),
                   max_new=(2, 1), tier_pages=3),
}


class PoolModel:
    """Wraps a REAL PagePool and mirrors the scheduler's host-side ops
    (paged/scheduler.py, spec/server.py) at op granularity. Op-scope
    invariants (cow-write, defrag-preserve) are checked inline where the
    write/remap happens and accumulate in `self.violations`; state-scope
    invariants are evaluated by the checker after each op.

    `mutations` injects seeded defects for the fixture tests:
      cow_bypass          — admission maps a shared donor tail page in
                            place instead of COW-cloning it;
      scratch_preregister — speculative verify registers its tree
                            scratch page before the commit;
      scale_cow_drop      — the COW clone copies the page payload but
                            not its scale-sidecar entry;
      scale_realloc_leak  — allocation hands out a page without
                            resetting its previous tenant's scale;
      swap_free_skip      — drain-and-swap detaches live owners but
                            leaves their pages allocated in the adopted
                            pool (carried requests re-admit and the old
                            pages leak with no owner);
      scale_defrag_drop   — defrag permutes page payloads but leaves
                            the scale sidecar at the old slots;
      spill_scale_drop    — the spill payload carries the page content
                            but ZEROES its scale-sidecar state: a fetch
                            (possibly on another server) dequantizes
                            the int8 rows under the wrong scale.

    The quantized-pool scale sidecar is modeled as a pair of per-page
    tags: `content_tag` is the spec truth — a bounded
    writes-since-alloc counter (capped at page_size, so the state space
    stays finite) stamped at every row write, copied by COW, permuted
    by defrag, reset at alloc, kept by LRU revival; `scale_of` mirrors
    the ops the implementation's sidecar actually performs (the seeded
    mutations above each skip exactly one of them). The scale-sidecar
    invariant is scale_of == content_tag on every reachable page.
    """

    def __init__(self, pool_factory=None, *, num_pages: int,
                 page_size: int, slots: int, spec_nodes: int,
                 prompts, max_new, tier_pages: int = 0,
                 mutations: Tuple[str, ...] = ()):
        self.P = int(page_size)
        self.slots = int(slots)
        self.spec_nodes = int(spec_nodes)
        self.mutations = tuple(mutations)
        max_rows = max(len(p) + m for p, m in zip(prompts, max_new)) \
            + self.spec_nodes
        self.max_pages = -(-max_rows // self.P)
        factory = pool_factory or PagePool
        self.pool = factory(num_pages, page_size, self.max_pages)
        self.reqs = [_Req(p, m) for p, m in zip(prompts, max_new)]
        self.committed: Dict[int, int] = {}  # page -> committed K/V rows
        self.scale_of: Dict[int, int] = {}     # impl's sidecar mirror
        self.content_tag: Dict[int, int] = {}  # spec's content truth
        self.violations: List[str] = []
        self.tier = None
        if tier_pages:
            # drive the REAL spill/fetch code (pool._spill_page,
            # _fetch_full, spill_request, spill_oldest, prefetch) with
            # bookkeeping-mirror payloads instead of device buffers: a
            # payload is (content_tag, scale_of, committed) at read time
            from flexflow_tpu.disagg.host_tier import HostTier

            self.tier = HostTier(int(tier_pages))
            self.pool.attach_tier(self.tier, self._tier_read_model,
                                  self._tier_write_model)

    # -- host-tier payload mirrors (tiered config) -------------------------

    def _tier_read_model(self, page: int):
        scale = self.scale_of.get(page, 0)
        if "spill_scale_drop" in self.mutations:
            # SEEDED DEFECT: the spill packs the page's rows but not its
            # scale-sidecar entry — the payload lands in the tier with a
            # zeroed scale state and every fetch restores garbage
            scale = 0
        return (self.content_tag.get(page, 0), scale,
                self.committed.get(page, 0))

    def _tier_write_model(self, page: int, payload):
        content, scale, committed = payload
        self.content_tag[page] = content
        self.scale_of[page] = scale
        self.committed[page] = committed

    # -- bookkeeping helpers ----------------------------------------------

    def clone(self) -> "PoolModel":
        return copy.deepcopy(self)

    def owners(self) -> Dict[int, List[int]]:
        return {i: r.pages for i, r in enumerate(self.reqs)
                if r.state == "active"}

    def _seq(self, req: _Req) -> Tuple[int, ...]:
        return req.prompt + tuple(req.tokens)

    def _next_token(self, req: _Req) -> int:
        # deterministic greedy stand-in: a pure function of the prefix,
        # so identical prompts emit identical streams (maximal sharing —
        # the token-identity property the real servers assert)
        s = self._seq(req)
        return (sum(s) * 31 + len(s) * 7) % 5 + 10

    def _alloc(self, n: int) -> Optional[List[int]]:
        pages = self.pool.alloc(n)
        if pages is not None:
            for p in pages:
                self.committed[p] = 0  # fresh/recycled content is garbage
                self.content_tag[p] = 0
                if "scale_realloc_leak" not in self.mutations:
                    # mirrors scheduler._reset_page_scales at every
                    # allocation site; the mutation keeps the previous
                    # tenant's scale on the recycled page
                    self.scale_of[p] = 0
                else:
                    self.scale_of.setdefault(p, 0)
        return pages

    def _write_row(self, req: _Req, row: int, scratch: bool = False):
        """One K/V row write through the request's page list, with the
        cow-write discipline checked at the write itself."""
        idx = row // self.P
        if idx >= len(req.pages):
            self.violations.append(
                f"cow-write: row {row} written past the page list "
                f"({len(req.pages)} pages)")
            return
        page = req.pages[idx]
        rc = self.pool.refcount(page)
        if rc != 1:
            self.violations.append(
                f"cow-write: row {row} written into page {page} with "
                f"refcount {rc} (shared pages are cloned, never written "
                "in place)")
        for kind, h in self.pool._keys_of.get(page, []):
            if kind == "full":
                self.violations.append(
                    f"cow-write: row {row} written into full-registered "
                    f"page {page} (published rows are immutable)")
            else:
                ent = self.pool._partial.get(h)
                if ent and ent[0] == page and row % self.P < len(ent[1]):
                    self.violations.append(
                        f"cow-write: row {row} overwrites the published "
                        f"partial tail (rows [0, {len(ent[1])})) of page "
                        f"{page}")
        if not scratch:
            c = self.committed.get(page, 0)
            self.committed[page] = max(c, row % self.P + 1)
        # every row write (scratch included — verify rewrites draft K/V)
        # changes the page's content AND grows its quantization scale
        # atomically (quantized_append); the bounded counter keeps BFS
        # finite while still distinguishing stale from current scales
        self.content_tag[page] = min(self.P,
                                     self.content_tag.get(page, 0) + 1)
        self.scale_of[page] = min(self.P, self.scale_of.get(page, 0) + 1)

    # -- publication (mirrors _publish_prefix/_publish_tail) --------------

    def _publish_prefix(self, req: _Req, valid: int):
        P = self.P
        target = min(valid // P, len(req.pages))
        if req.hashed_blocks >= target:
            return
        seq = self._seq(req)
        chain = self.pool.chain_hashes(list(seq[:target * P]))
        for b in range(req.hashed_blocks, target):
            self.pool.register_full(req.pages[b], chain[b])
        req.hashed_blocks = target

    def _publish_tail(self, req: _Req):
        if not req.pages:
            return
        P = self.P
        valid = max(req.pos, req.prefill_pos)
        self._publish_prefix(req, valid)
        full = req.hashed_blocks
        tail = valid - full * P
        if tail > 0 and full < len(req.pages):
            seq = self._seq(req)
            chain = self.pool.chain_hashes(list(seq[:full * P]))
            parent = chain[-1] if chain else EMPTY_HASH
            self.pool.register_partial(req.pages[full], parent,
                                       list(seq[full * P:valid]))

    # -- ops ---------------------------------------------------------------

    def _admission_pages(self, req: _Req) -> int:
        # base: prompt + the first decode write row; spec: prompt + the
        # whole first verify tree (spec/server.py:_admission_pages)
        extra = self.spec_nodes if self.spec_nodes else 1
        need = min(len(self._seq(req)) + extra, self.max_pages * self.P)
        return self.pool.pages_for(need)

    def enabled_ops(self) -> List[str]:
        ops = []
        active = sum(1 for r in self.reqs if r.state == "active")
        for i, r in enumerate(self.reqs):
            if r.state == "queued" and active < self.slots \
                    and self._admission_pages(r) <= self.pool.free_pages:
                ops.append(f"admit({i})")
        for i, r in enumerate(self.reqs):
            if r.state == "active":
                ops.append(f"step({i})")
        for i, r in enumerate(self.reqs):
            if r.state == "active":
                ops.append(f"preempt({i})")
        if self.pool._refs or self.pool._lru:
            ops.append("defrag")
        if active:
            ops.append("swap")
        if self.tier is not None:
            if self.pool._lru:
                ops.append("spill")      # proactive spill_oldest
            if self.pool.free_pages >= 1:
                # prefetch always lands when a page is allocatable
                for j in range(len(self.tier.hashes())):
                    ops.append(f"fetch({j})")
            for i, r in enumerate(self.reqs):
                # the prefill->decode handoff fires at prefill
                # completion; post-prefill is when a request's pages
                # can leave through the tier
                if r.state == "active" \
                        and r.prefill_pos >= r.prefill_target:
                    ops.append(f"adopt({i})")
        return ops

    def apply(self, label: str):
        if label == "defrag":
            return self._op_defrag()
        if label == "swap":
            return self._op_swap()
        if label == "spill":
            return self._op_spill()
        op, rid = label[:-1].split("(")
        return getattr(self, "_op_" + op)(int(rid))

    def _op_admit(self, i: int):
        """Mirror of PagedGenerationServer._admit: prefix lookup, the
        last-prompt-token clamp, COW of the boundary page, private
        allocation of the suffix, and the transient-shortfall rollback."""
        req, pool, P = self.reqs[i], self.pool, self.P
        seq = self._seq(req)
        n = len(seq)
        shared, cached, cow = pool.lookup(list(seq))
        start = min(cached, n - 1)
        b0 = start // P
        keep = shared[:b0]
        cow_src = cow if cow is not None else (
            shared[b0] if b0 < len(shared) else None)
        total = pool.pages_for(n)
        fresh = self._alloc(total - b0)
        if fresh is None:
            # transient shortfall: drop the hits, retry as full recompute
            pool.free(keep + ([cow_src] if cow_src is not None else []))
            if cached > 0:
                pool.hit_tokens -= cached
                pool.hits -= 1
                pool.misses += 1
            shared, keep, cached, cow_src = [], [], 0, None
            start, b0 = 0, 0
            fresh = self._alloc(total)
            if fresh is None:
                return  # stays queued (the enabled gate was optimistic)
        if cached > start:
            pool.hit_tokens -= cached - start
        pages = keep + fresh
        req.pages = pages
        if cow_src is not None:
            if "cow_bypass" in self.mutations:
                # SEEDED DEFECT: map the shared donor page in place of
                # the private clone — writes past the shared rows now
                # mutate a page other owners (or the index) still name
                pool.free([pages[b0]])
                pages[b0] = cow_src
            else:
                # COW clone: rows below `start` carry over as committed;
                # copy_page tree-maps over EVERY cache leaf, so the
                # clone inherits the donor's content AND scale entry
                self.committed[pages[b0]] = max(0, start - b0 * P)
                self.content_tag[pages[b0]] = \
                    self.content_tag.get(cow_src, 0)
                if "scale_cow_drop" not in self.mutations:
                    self.scale_of[pages[b0]] = self.scale_of.get(cow_src, 0)
                pool.free([cow_src])
        req.prefill_pos = start
        req.prefill_target = n
        req.pos = 0
        req.hashed_blocks = min(b0, n // P)
        req.state = "active"

    def _op_step(self, i: int):
        req = self.reqs[i]
        if req.prefill_pos < req.prefill_target:
            self._prefill_chunk(req)
        else:
            self._decode(req)

    def _prefill_chunk(self, req: _Req):
        """One page-size chunk of chunked prefill, with per-block
        publication; the finishing chunk publishes the prompt tail and
        samples the first token (scheduler.py:_prefill_tick)."""
        n = req.prefill_target
        take = min(self.P, n - req.prefill_pos)
        for r in range(req.prefill_pos, req.prefill_pos + take):
            self._write_row(req, r)
        req.prefill_pos += take
        self._publish_prefix(req, req.prefill_pos)
        if req.prefill_pos >= n:
            self._publish_tail(req)
            tok = self._next_token(req)
            req.pos = n
            req.tokens.append(tok)
            self._finish_if_done(req)

    def _grow(self, req: _Req, target_pages: int) -> bool:
        """_ensure_pages for one request: grow to `target_pages`,
        preempting the youngest OTHER active request under pressure
        (or self when none — a stall, never a wrong answer)."""
        while len(req.pages) < target_pages:
            got = self._alloc(1)
            if got is not None:
                req.pages.append(got[0])
                continue
            others = [r for r in self.reqs
                      if r is not req and r.state == "active"]
            if others:
                self._do_preempt(others[-1])
            else:
                self._do_preempt(req)
                return False
        return True

    def _decode(self, req: _Req):
        rows = self.max_pages * self.P
        if self.spec_nodes:
            # speculative verify: grow to cover the whole tree, write
            # its scratch rows past the committed head, then commit
            target = self.pool.pages_for(min(req.pos + self.spec_nodes,
                                             rows))
            if not self._grow(req, target):
                return
            hi = min(req.pos + self.spec_nodes, rows)
            for r in range(req.pos, hi):
                self._write_row(req, r, scratch=True)
            if "scratch_preregister" in self.mutations and hi > req.pos:
                # SEEDED DEFECT: publish the drafted tree before the
                # commit — the page holding the tree's LAST scratch row
                # reaches the hash index while its rows are still
                # uncommitted draft K/V
                idx = (hi - 1) // self.P
                if idx < len(req.pages):
                    self.pool.register_full(
                        req.pages[idx], f"scratch:{self._seq(req)}")
            # commit the accepted path: scratch rows [pos, pos+L) become
            # committed K/V, pos advances, tokens append (greedy stand-in
            # accepts as deep a path as the budget allows)
            L = min(self.spec_nodes, req.max_new - len(req.tokens),
                    hi - req.pos)
            for r in range(req.pos, req.pos + L):
                page = req.pages[r // self.P]
                c = self.committed.get(page, 0)
                self.committed[page] = max(c, r % self.P + 1)
            for _ in range(L):
                req.tokens.append(self._next_token(req))
            req.pos += L
        else:
            if not self._grow(req, self.pool.pages_for(req.pos + 1)):
                return
            self._write_row(req, req.pos)
            req.pos += 1
            req.tokens.append(self._next_token(req))
        self._publish_prefix(req, req.pos)
        self._finish_if_done(req)

    def _finish_if_done(self, req: _Req):
        if len(req.tokens) >= req.max_new:
            self._publish_tail(req)
            self.pool.free(list(reversed(req.pages)))  # leaf-first
            req.pages = []
            req.state = "done"

    def _do_preempt(self, req: _Req):
        self._publish_tail(req)
        self.pool.free(list(reversed(req.pages)))  # leaf-first
        req.pages = []
        req.pos = 0
        req.prefill_pos = 0
        req.prefill_target = 0
        req.hashed_blocks = 0
        req.state = "queued"  # requeues; resume re-attaches via lookup

    def _op_preempt(self, i: int):
        self._do_preempt(self.reqs[i])

    def _op_spill(self):
        """Proactive pressure relief: PagePool.spill_oldest moves the
        LRU-oldest dead page's payload into the tier and frees it."""
        self.pool.spill_oldest()

    def _op_fetch(self, j: int):
        """PagePool.prefetch of the j-th spilled hash (sorted for a
        deterministic label): the payload lands in a fresh page, parked
        dead-cached for the next lookup."""
        hashes = sorted(self.tier.hashes())
        if j < len(hashes):
            self.pool.prefetch(hashes[j])

    def _op_adopt(self, i: int):
        """The prefill->decode handoff (disagg/workers.py
        PrefillWorker._on_prefill_complete): publish, spill the
        request's pages into the tier, free, and requeue with tokens
        intact — the later admit(i) re-attaches via lookup's
        transparent fetch, modeling the decode worker's admission
        (one pool plays both sides; the tier is the channel)."""
        req = self.reqs[i]
        self._publish_tail(req)
        self.pool.spill_request(req.pages)
        self.pool.free(list(reversed(req.pages)))  # leaf-first
        req.pages = []
        req.pos = 0
        req.prefill_pos = 0
        req.prefill_target = 0
        req.hashed_blocks = 0
        req.state = "queued"

    def _op_swap(self):
        """Strategy change in flight: mirror of the drain-and-swap
        handoff (scheduler._detach_active + the successor's
        adopt_pool_from/absorb_requests). Every live owner publishes
        its tail, releases its pages into the pool the successor
        adopts, and carries over as queued with its emitted tokens
        intact — re-admission re-attaches via prefix lookup, so the
        carried streams stay token-identical. Unlike preempt (one
        victim under page pressure) this detaches ALL actives
        atomically between ticks."""
        for req in self.reqs:
            if req.state != "active":
                continue
            self._publish_tail(req)
            if "swap_free_skip" in self.mutations:
                # SEEDED DEFECT: the detach hands the request to the
                # successor but never frees its pages — the adopted
                # pool keeps refcounts nobody owns, and the carried
                # request double-allocates on re-admission
                pass
            else:
                self.pool.free(list(reversed(req.pages)))  # leaf-first
            req.pages = []
            req.pos = 0
            req.prefill_pos = 0
            req.prefill_target = 0
            req.hashed_blocks = 0
            req.state = "queued"

    def _op_defrag(self):
        """pool.defrag() + the scheduler-side owner-table rewrite, with
        the defrag-preserve invariant checked against the pre-state."""
        pool = self.pool
        pre_refs = dict(pool._refs)
        pre_lru = list(pool._lru)
        pre_full = dict(pool._full)
        pre_partial = dict(pool._partial)
        allocated = set(pre_refs) | set(pre_lru)
        perm, old_to_new = pool.defrag()

        def m(p):
            return int(old_to_new[p])

        v = []
        if sorted(int(x) for x in perm) != list(range(pool.num_pages)):
            v.append("perm is not a permutation of the page ids")
        if m(0) != 0:
            v.append("the null page was remapped")
        if pool._refs != {m(p): r for p, r in pre_refs.items()}:
            v.append(f"refcounts not preserved: {pre_refs} -> "
                     f"{pool._refs} under {dict((p, m(p)) for p in pre_refs)}")
        if list(pool._lru) != [m(p) for p in pre_lru]:
            v.append("the LRU dead list (or its order) was not preserved")
        if pool._full != {h: m(p) for h, p in pre_full.items()}:
            v.append("the full-block hash index was not preserved")
        if pool._partial != {h: (m(p), t)
                             for h, (p, t) in pre_partial.items()}:
            v.append("the partial-tail hash index was not preserved")
        self.violations += [f"defrag-preserve: {s}" for s in v]
        for r in self.reqs:
            r.pages = [m(p) for p in r.pages]
        self.committed = {m(p): c for p, c in self.committed.items()
                          if p in allocated}
        self.content_tag = {m(p): t for p, t in self.content_tag.items()
                            if p in allocated}
        if "scale_defrag_drop" in self.mutations:
            # SEEDED DEFECT: the payload permutation ran but the scale
            # sidecar was left behind — page m(p)'s int8 rows now
            # dequantize under whatever scale sat at slot m(p) before
            self.scale_of = {p: t for p, t in self.scale_of.items()
                             if p in allocated}
        else:
            self.scale_of = {m(p): t for p, t in self.scale_of.items()
                             if p in allocated}

    # -- canonical state -------------------------------------------------

    def key(self) -> tuple:
        """Canonical serialization for BFS dedup. The free list is
        SORTED (a symmetry reduction: its order only selects which
        interchangeable page id the next alloc hands out); the LRU keeps
        its order (eviction order is semantic). Prefix-cache counters
        are excluded — they never influence a transition."""
        pool = self.pool
        reqs = tuple((r.state, tuple(r.tokens), tuple(r.pages), r.pos,
                      r.prefill_pos, r.prefill_target, r.hashed_blocks)
                     for r in self.reqs)
        keys_of = tuple(sorted((p, tuple(sorted(ks)))
                               for p, ks in pool._keys_of.items() if ks))
        live = set(pool._refs) | set(pool._lru)
        return (reqs,
                tuple(sorted(pool._free)),
                tuple(sorted(pool._refs.items())),
                tuple(pool._lru),
                tuple(sorted(pool._full.items())),
                tuple(sorted(pool._partial.items())),
                keys_of,
                tuple(sorted((p, c) for p, c in self.committed.items()
                             if p in live)),
                # stale entries on FREE pages are excluded: a correct
                # model resets them at the next alloc, so they never
                # influence a transition (the realloc-leak mutation is
                # caught at the alloc itself, before any dedup)
                tuple(sorted((p, t) for p, t in self.scale_of.items()
                             if p in live)),
                tuple(sorted((p, t) for p, t in self.content_tag.items()
                             if p in live)),
                # tier entries IN ORDER (its LRU eviction order is
                # semantic, like the pool's dead list)
                (tuple((h, self.tier.peek(h))
                       for h in self.tier.hashes())
                 if self.tier is not None else ()))


class CheckResult:
    """Outcome of one bounded exploration."""

    def __init__(self, config: str, explored: int, reached: int,
                 hits: List[Tuple[str, str, Tuple[str, ...]]],
                 truncated: bool):
        self.config = config
        self.explored = explored
        self.reached = reached
        self.hits = hits            # (invariant, detail, minimal trace)
        self.truncated = truncated


def _state_violations(state: PoolModel) -> List[str]:
    v = (list(state.violations)
         + inv.check_pool(state.pool, state.owners())
         + inv.check_committed(state.pool, state.committed)
         + inv.check_scales(state.pool, state.scale_of,
                            state.content_tag))
    if state.tier is not None:
        # unpack the mirror payloads: scales must have traveled
        tier_scale: Dict[str, int] = {}
        tier_content: Dict[str, int] = {}
        for h in state.tier.hashes():
            payload = state.tier.peek(h)
            if payload is not None:
                tier_content[h], tier_scale[h], _ = payload
        v += inv.check_tier_scales(state.pool, tier_scale, tier_content)
    return v


def model_check(config: str = "base", pool_factory=None,
                mutations: Tuple[str, ...] = (),
                max_states: int = 400_000,
                max_findings: int = 4) -> CheckResult:
    """BFS over every reachable state of the bounded scenario. The
    first state violating an invariant yields that invariant's MINIMAL
    counterexample (BFS explores by depth); violating states are not
    expanded further."""
    root = PoolModel(pool_factory=pool_factory,
                     mutations=tuple(mutations), **CONFIGS[config])
    seen: Set[tuple] = {root.key()}
    frontier: deque = deque([(root, ())])
    hits: List[Tuple[str, str, Tuple[str, ...]]] = []
    explored = 0
    while frontier and len(hits) < max_findings \
            and explored < max_states:
        state, trace = frontier.popleft()
        explored += 1
        for label in state.enabled_ops():
            child = state.clone()
            child.violations = []
            child.apply(label)
            ctrace = trace + (label,)
            found = _state_violations(child)
            if found:
                for msg in found:
                    name = msg.split(":", 1)[0]
                    if all(h[0] != name for h in hits):
                        hits.append((name, msg, ctrace))
                continue  # a broken state's successors prove nothing new
            k = child.key()
            if k not in seen:
                seen.add(k)
                frontier.append((child, ctrace))
    return CheckResult(config, explored, len(seen), hits,
                       truncated=bool(frontier) and explored >= max_states)


def replay(trace, config: str = "base", pool_factory=None,
           mutations: Tuple[str, ...] = ()) -> List[str]:
    """Re-execute a counterexample trace from the initial state and
    return every violation it produces (empty = does not reproduce)."""
    state = PoolModel(pool_factory=pool_factory,
                      mutations=tuple(mutations), **CONFIGS[config])
    out: List[str] = []
    for label in trace:
        state.violations = []
        state.apply(label)
        out += _state_violations(state)
    return out


# ---------------------------------------------------------------------------
# lint arm: AST checks over serving.py / paged/ / spec/

LINT_ROOTS = ("serving.py", "paged", "spec", "serving_autopilot.py",
              "disagg")
# the host-side state-machine files the page/table write checks cover
# (kernel files write K/V rows THROUGH the table by design)
_STATE_FILE_BASENAMES = {"scheduler.py", "pool.py", "server.py"}
_COW_FNS = {"copy_page",
            # alloc-time scale-sidecar zeroing: runs only on pages just
            # handed out by the allocator (exclusively owned, nothing
            # published), part of the allocation lifecycle like the
            # table writes in _admit/_ensure_pages
            "reset_page_scales",
            # host-tier restore: writes a spilled payload into a page
            # the allocator JUST handed out (_fetch_full pins it at
            # refcount 1 before anything can share it) — the fetch-side
            # twin of the alloc lifecycle, never a shared-page write
            "write_page"}
_TABLE_FNS = {"__init__", "_admit", "_apply_defrag", "_release_slot",
              "_vacate",
              "_evict", "_ensure_pages",
              # the release arm of drain-and-swap: joins the loop, frees
              # every slot's pages, then zeroes the rows — the model
              # checker's `swap` op mirrors it
              "_detach_active"}
_DIRECTIVES = ("lock-ok", "cow-ok", "table-ok", "pool-ok")


def default_lint_paths() -> List[str]:
    base = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(base, p) for p in LINT_ROOTS]


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


class _FileLint:
    """Per-file lint state: comments, pragma bookkeeping, findings."""

    def __init__(self, rel: str, src: str, tree: ast.Module):
        self.rel = rel
        self.tree = tree
        self.comments = _comment_map(src)
        self.used_pragmas: Set[int] = set()
        self.findings: List[Finding] = []

    def add(self, severity: str, code: str, lineno: int, msg: str,
            directive: str, *extra_linenos: int):
        for ln in (lineno,) + extra_linenos:
            d = _directive_of(self.comments.get(ln, ""))
            if d in (directive, "ignore"):
                self.used_pragmas.add(ln)
                return
        self.findings.append(Finding(
            "poolcheck", severity, code, f"{self.rel}:{lineno}", msg))

    def stale_pragmas(self):
        for ln, txt in sorted(self.comments.items()):
            if _directive_of(txt) is not None \
                    and ln not in self.used_pragmas:
                self.findings.append(Finding(
                    "poolcheck", "info", "stale-pragma",
                    f"{self.rel}:{ln}",
                    f"'# fflint: {_directive_of(txt)}' pragma no longer "
                    "suppresses any poolcheck finding — delete it"))


def _is_at_set(node: ast.Call) -> bool:
    """x.at[...].set(...) / .add(...) — the functional buffer write."""
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in ("set", "add")
            and isinstance(f.value, ast.Subscript)
            and isinstance(f.value.value, ast.Attribute)
            and f.value.value.attr == "at")


def _fn_of(tree: ast.Module) -> Dict[int, str]:
    """lineno -> name of the function whose body contains it (innermost
    def wins), for allowlist checks."""
    spans: List[Tuple[int, int, str]] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            spans.append((node.lineno, node.end_lineno or node.lineno,
                          node.name))
    out: Dict[int, str] = {}
    for lo, hi, name in sorted(spans):  # later/inner spans overwrite
        for ln in range(lo, hi + 1):
            out[ln] = name
    return out


def _lint_state_file(fl: _FileLint):
    """page-write / table-write checks, only on the state-machine
    files (scheduler.py / pool.py / spec server.py)."""
    fn_of = _fn_of(fl.tree)
    for node in ast.walk(fl.tree):
        if isinstance(node, ast.Call) and _is_at_set(node):
            fn = fn_of.get(node.lineno, "<module>")
            if fn not in _COW_FNS:
                fl.add(
                    "error", "page-write-outside-cow", node.lineno,
                    f"in {fn}(): .at[...].{node.func.attr} writes a "
                    "cache buffer outside the COW clone helper — pool "
                    "pages may be shared (refcount > 1) or published; "
                    "route the write through copy_page / the jitted "
                    "step, or annotate '# fflint: cow-ok (reason)'",
                    "cow-ok")
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                base = t.value if isinstance(t, ast.Subscript) else t
                if _dotted(base) == ("self", "_tables"):
                    fn = fn_of.get(node.lineno, "<module>")
                    if fn not in _TABLE_FNS:
                        fl.add(
                            "error", "table-write-outside-admission",
                            node.lineno,
                            f"in {fn}(): page-table mutation outside "
                            "the admission/growth/defrag/release "
                            f"lifecycle ({sorted(_TABLE_FNS)}) — table "
                            "contents must stay a pure function of the "
                            "pool bookkeeping, or annotate "
                            "'# fflint: table-ok (reason)'",
                            "table-ok")


def _lint_pool_private(fl: _FileLint):
    """pool._x access outside paged/pool.py."""
    if os.path.basename(fl.rel) == "pool.py":
        return
    for node in ast.walk(fl.tree):
        if isinstance(node, ast.Attribute) \
                and node.attr.startswith("_") \
                and not node.attr.startswith("__"):
            d = _dotted(node.value)
            if d and d[-1] == "pool":
                fl.add(
                    "warning", "pool-private-access", node.lineno,
                    f"touches pool.{node.attr} — PagePool underscore "
                    "state is maintained by its own methods; going "
                    "around them breaks the invariant catalog "
                    "(docs/paged.md), or annotate "
                    "'# fflint: pool-ok (reason)'",
                    "pool-ok")


# -- lock discipline ---------------------------------------------------------


class _LockScanner(ast.NodeVisitor):
    """Flag unlocked reads of loop-owned fields (and pool state) in ONE
    public method of a threaded server class. `owned` and `lock_attrs`
    come from racecheck's whole-repo lock model (see _lint_locks)."""

    def __init__(self, fl: _FileLint, cls: str, meth, owned: Set[str],
                 lock_attrs: Optional[Set[str]] = None):
        self.fl = fl
        self.cls = cls
        self.meth = meth
        self.owned = owned
        self.lock_attrs = lock_attrs or {"_lock"}
        self.lock_depth = 0
        self.pool_aliases: Set[str] = set()

    def _flag(self, lineno: int, what: str):
        self.fl.add(
            "warning", "unlocked-cross-thread-read", lineno,
            f"in {self.cls}.{self.meth.name}(): reads {what} without "
            "holding self._lock while the scheduler-loop thread mutates "
            "it — take the lock, or annotate a deliberate relaxed read "
            "'# fflint: lock-ok (reason)'",
            "lock-ok", self.meth.lineno)

    def visit_FunctionDef(self, node):
        return  # nested defs are separate (deferred) execution contexts

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node):
        locked = any(
            d is not None and len(d) == 2 and d[0] == "self"
            and d[1] in self.lock_attrs
            for d in (_dotted(i.context_expr) for i in node.items))
        if locked:
            self.lock_depth += 1
            for stmt in node.body:
                self.visit(stmt)
            self.lock_depth -= 1
        else:
            self.generic_visit(node)

    def visit_Assign(self, node):
        if isinstance(node.value, ast.Attribute) \
                and _dotted(node.value) == ("self", "pool"):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.pool_aliases.add(t.id)
            return  # the alias binding itself is not a state read
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if isinstance(node.ctx, ast.Load) and self.lock_depth == 0:
            d = _dotted(node)
            if d and d[0] == "self" and len(d) >= 2:
                if len(d) == 2 and d[1] in self.owned:
                    self._flag(node.lineno, f"self.{d[1]}")
                    return
                if len(d) >= 3 and d[1] == "pool":
                    self._flag(node.lineno,
                               f"self.pool.{'.'.join(d[2:])}")
                    return
            elif d and d[0] in self.pool_aliases and len(d) >= 2:
                self._flag(node.lineno, f"{'.'.join(d)} (pool state)")
                return
        self.generic_visit(node)


def _lint_locks(file_lints: List[_FileLint]):
    """Delegates to racecheck's whole-repo lock model (ONE lock model in
    the tree): racecheck closes the class hierarchy both ways and infers
    threadedness, loop-owned fields, and lock-guarded fields; this arm
    keeps poolcheck's historical public-surface unlocked-read scan over
    that model. Non-transitive within a method, like hostsync: each
    method's own AST only."""
    from flexflow_tpu.analysis import racecheck

    units = [(fl.rel, fl.tree) for fl in file_lints]
    model = racecheck.build_lock_model(units)
    fl_by_rel = {fl.rel: fl for fl in file_lints}
    for name in sorted(model.classes):
        cm = model.classes[name]
        fl = fl_by_rel.get(cm.rel)
        if fl is None:
            continue
        if not model.family_threaded(name):
            continue
        # cross-thread state = poolcheck's historical loop-owned fields
        # UNION racecheck's lock-guarded fields (a field someone takes a
        # lock to write is cross-thread by that very act)
        owned = model.family_owned(name) \
            | set(model.family_guarded(name))
        lock_attrs = model.family_lock_attrs(name) | {"_lock"}
        for meth in cm.public_method_nodes():
            scanner = _LockScanner(fl, name, meth, owned, lock_attrs)
            for stmt in meth.body:
                scanner.visit(stmt)


def lint_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    fls = _collect_file_lints([path], rel_override=rel)
    _lint_locks(fls)
    out: List[Finding] = []
    for fl in fls:
        fl.stale_pragmas()
        out += fl.findings
    out.sort(key=lambda f: f.where)
    return out


def _collect_file_lints(paths: List[str],
                        rel_override: Optional[str] = None
                        ) -> List[_FileLint]:
    files: List[Tuple[str, str]] = []  # (full, rel)
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
    out: List[_FileLint] = []
    for full, rel in files:
        with open(full) as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=full)
        except SyntaxError as e:
            fl = _FileLint(rel, "", ast.Module(body=[], type_ignores=[]))
            fl.findings.append(Finding(
                "poolcheck", "error", "syntax-error",
                f"{rel}:{e.lineno}", str(e)))
            out.append(fl)
            continue
        fl = _FileLint(rel, src, tree)
        if os.path.basename(rel) in _STATE_FILE_BASENAMES:
            _lint_state_file(fl)
        _lint_pool_private(fl)
        out.append(fl)
    return out


def lint_paths(paths: List[str]) -> List[Finding]:
    fls = _collect_file_lints(paths)
    _lint_locks(fls)
    out: List[Finding] = []
    for fl in fls:
        fl.stale_pragmas()
        out += fl.findings
    out.sort(key=lambda f: f.where)
    return out


# ---------------------------------------------------------------------------
# the pass


def _model_findings(ctx: AnalysisContext) -> List[Finding]:
    findings: List[Finding] = []
    mutations = tuple(ctx.poolcheck_mutations or ())
    trace_dir = ctx.poolcheck_trace_dir
    summary: Dict[str, object] = {"configs": {}}
    total = 0
    for config in sorted(CONFIGS):
        res = model_check(config,
                          pool_factory=ctx.poolcheck_pool_factory,
                          mutations=mutations)
        total += res.explored
        summary["configs"][config] = {
            "explored_states": res.explored,
            "distinct_states": res.reached,
            "violations": len(res.hits),
        }
        for name, msg, trace in res.hits:
            detail = msg.split(":", 1)[1].strip() if ":" in msg else msg
            entry = inv.by_name(name) if _known(name) else None
            findings.append(Finding(
                "poolcheck", "error", f"inv-{name}",
                f"poolcheck:model/{config}",
                f"invariant '{name}' violated — {detail}. "
                f"Spec: {entry.description if entry else '?'}. "
                f"Minimal counterexample ({len(trace)} ops): "
                f"{' -> '.join(trace)}"))
            if trace_dir:
                os.makedirs(trace_dir, exist_ok=True)
                fn = os.path.join(trace_dir,
                                  f"{config}-inv-{name}.json")
                with open(fn, "w") as f:
                    json.dump({"config": config, "invariant": name,
                               "detail": detail, "trace": list(trace),
                               "replay": "flexflow_tpu.analysis."
                                         "poolcheck.replay(trace, "
                                         f"config={config!r})"},
                              f, indent=1)
        if res.truncated:
            findings.append(Finding(
                "poolcheck", "warning", "model-check-truncated",
                f"poolcheck:model/{config}",
                f"exploration stopped at {res.explored} states with the "
                "frontier non-empty — the bounded state space was NOT "
                "fully explored; raise max_states"))
    summary["explored_states"] = total
    ctx.poolcheck_summary = summary
    findings.append(Finding(
        "poolcheck", "info", "model-check-summary", "poolcheck:model",
        f"explored {total} states across {len(CONFIGS)} bounded "
        f"configs ({', '.join(sorted(CONFIGS))}); "
        f"{len(inv.CATALOG)} invariants asserted at every state"))
    return findings


def _known(name: str) -> bool:
    try:
        inv.by_name(name)
        return True
    except KeyError:
        return False


@register_pass("poolcheck")
def poolcheck_pass(ctx: AnalysisContext) -> List[Finding]:
    paths = ctx.src_paths if ctx.src_paths is not None \
        else default_lint_paths()
    findings = lint_paths(paths)
    if not ctx.poolcheck_lint_only:
        findings += _model_findings(ctx)
    return findings
