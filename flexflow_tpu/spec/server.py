"""Speculative continuous batching over the paged KV cache.

Each decode tick becomes a TREE-VERIFY step: a host-side drafter proposes
a token tree per live slot (flexflow_tpu.spec.drafter), one jitted
forward scores every node under the tree-attention mask
(Executor.ragged_step_fn), and a greedy host-side walk accepts the longest
verified path. Rollback is nearly free on the paged cache: the accepted
path's K/V rows are copied onto the contiguous committed positions
(Executor.paged_commit_fn — one fixed-shape gather/scatter), `pos`
advances by the tokens emitted, and every rejected row simply sits past
the new write head where the absolute-position mask already hides it.
No page is copied, no cache is rebuilt.

Tick flow (vs the base scheduler's one-token step):
  1. admit (base policy, but the page gate also covers the tree width)
  2. grow pages to cover pos + max_nodes rows (tree scratch included)
  3. draft: trailing-context trees for the live GREEDY slots
  4. ONE ragged verify launch: tree items for greedy slots (q_len =
     real node count), single-row items for temperature>0 slots, and
     NO rows at all for idle or mid-prefill slots
  5. accept: greedy argmax walk per slot; temperature>0 slots take only
     the root's sample (exactness under sampling needs rejection
     sampling — not implemented), so they decode at 1 token/step
  6. commit accepted rows, advance pos, append tokens, finish/free

Greedy output is token-identical to the non-speculative paged path by
construction: every emitted token is the model's argmax continuation of
its own committed prefix.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from flexflow_tpu import obs
from flexflow_tpu.paged.scheduler import PagedGenerationServer
from flexflow_tpu.serving import _GenRequest
from flexflow_tpu.spec.config import SpecConfig


class SpeculativePagedServer(PagedGenerationServer):
    """PagedGenerationServer whose decode tick verifies a drafted token
    tree (serve_generation(paged=True, speculate=SpecConfig(...))). Same
    public surface, admission, preemption, and defrag as the paged
    server; only the tick body and the page-budget accounting change."""

    def __init__(self, ff, spec: SpecConfig, slots: int = 4,
                 max_len: int = 512, eos_id: Optional[int] = None,
                 seed: int = 0, page_size: int = 64,
                 num_pages: Optional[int] = None, preemption: bool = True,
                 prefix_cache: bool = True, prefill_chunk: int = 64,
                 request_record_limit: Optional[int] = None,
                 kv_dtype: str = "auto",
                 reqlog_capacity: Optional[int] = None,
                 slo=None, slo_dump_dir: Optional[str] = None,
                 kv_quant_canary: Optional[int] = None,
                 serve_strategy=None, defer_start: bool = False,
                 host_tier=None):
        if not isinstance(spec, SpecConfig):
            raise TypeError(
                f"speculate must be a SpecConfig, got {type(spec).__name__}")
        self.spec = spec
        self.drafter = spec.build_drafter()
        ex = ff.executor
        # verify rides the base server's ragged step (_launch); only the
        # accepted-path row copy needs its own program
        self._commit = ex.paged_commit_fn()
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        # the page tables must address max_len + max_nodes rows: a verify
        # at pos close to max_len writes its tree past the committed head
        super().__init__(ff, slots=slots, max_len=max_len, eos_id=eos_id,
                         seed=seed, page_size=page_size,
                         num_pages=num_pages, preemption=preemption,
                         table_slack_tokens=spec.max_nodes,
                         prefix_cache=prefix_cache,
                         prefill_chunk=prefill_chunk,
                         request_record_limit=request_record_limit,
                         kv_dtype=kv_dtype,
                         reqlog_capacity=reqlog_capacity,
                         slo=slo, slo_dump_dir=slo_dump_dir,
                         kv_quant_canary=kv_quant_canary,
                         serve_strategy=serve_strategy,
                         defer_start=defer_start,
                         host_tier=host_tier)
        # per-tick draft acceptance rate (accepted / drafted this tick)
        self._h_accept = self.registry.histogram("spec_acceptance",
                                                 obs.RATIO_BUCKETS)

    def shape_config(self) -> dict:
        """Extend the paged launch-shape space with the verify tree:
        verify launches are (live, max_nodes) windows and the accepted
        path commits (slots, depth+1) rows (analysis.shapecheck)."""
        cfg = super().shape_config()
        cfg["spec_max_nodes"] = self.spec.max_nodes
        cfg["spec_depth"] = self.spec.depth
        return cfg

    # -- page accounting: the tree's scratch rows count --------------------

    def _table_rows(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def _peak_rows(self, prompt_len: int, max_new_tokens: int) -> int:
        # deepest verify runs at pos <= prompt+max_new-1 and touches
        # max_nodes rows beyond it
        return min(prompt_len + max_new_tokens - 1 + self.spec.max_nodes,
                   self._table_rows())

    def _admission_pages(self, req: _GenRequest) -> int:
        # admit only when prompt + first verify tree fit, so admission
        # cannot preempt on its very first tick
        return self.pool.pages_for(
            min(len(req.seq_tokens()) + self.spec.max_nodes,
                self._table_rows()))

    def _pages_target(self, req: _GenRequest) -> int:
        return min(self.pool.pages_for(req.pos + self.spec.max_nodes),
                   self.max_pages_per_seq)

    # -- metrics -----------------------------------------------------------

    def metrics(self) -> dict:  # fflint: lock-ok (relaxed metrics snapshot; int reads are atomic, staleness is fine for scraping)
        m = super().metrics()
        m["speculative"] = {
            "steps": self.spec_steps,
            "draft_tokens": self.spec_drafted,
            "accepted_tokens": self.spec_accepted,
            "emitted_tokens": self.spec_emitted,
            "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
            "accepted_tokens_per_step": (self.spec_emitted / self.spec_steps
                                         if self.spec_steps else 0.0),
        }
        return m

    # -- the speculative tick ----------------------------------------------

    def _loop_body(self, tr, ntr):
        while not self._stop.is_set():
            live = self._tick_prep()
            if live is None:
                continue
            # chunked prefill rides the same tick structure as the base
            # loop: mid-prefill slots advance one budgeted chunk, then
            # the decoding slots verify — a long prompt never stalls
            # in-flight speculation for more than the shared tick
            pre, live = self._split_live(live)
            if pre:
                self._prefill_tick(pre, tr, ntr)
            if not live:
                continue
            if all(self._active[s].temperature > 0.0 for s in live):
                # nothing to speculate on: sampled requests take one
                # token per step either way, so dispatch the plain
                # single-token tick instead of a max_nodes-wide verify
                self._decode_tick(live, tr, ntr)
                continue
            self._spec_tick(live, tr, ntr)

    def _spec_tick(self, live, tr, ntr):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.spec.tree import (
            accept_greedy,
            ancestor_masks,
            build_tree,
        )

        # drafting reads every token a slot has: take what is in flight
        # (a finishing chunk's first token, an all-sampled tick's picks)
        if self._retire("spec"):
            live = [s for s in live if self._active[s] is not None]
            if not live:
                return
        T = self.spec.max_nodes
        C = self.spec.depth + 1  # max rows committed per tick (path+bonus)
        # draft: one tree WORK ITEM per live greedy slot.
        # temperature>0 slots skip the drafter entirely — their
        # accept path is the root's sample only, so they pack as
        # single-row decode items instead of max_nodes-wide trees
        # (drafts would be paid for and thrown away, and would
        # dilute the acceptance metrics). Idle and mid-prefill slots
        # pack NOTHING.
        t0 = time.monotonic()
        tick_drafted = 0
        sp = obs.span("draft").__enter__()
        slots_of = []   # item index -> slot
        trees = {}
        tree_rows = []  # item indexes carrying a real tree
        parents = []
        for s in live:
            req = self._active[s]
            if req.temperature > 0.0:
                slots_of.append(s)      # 1-row item
                continue
            chains = self.drafter.draft(req.seq_tokens(),
                                        self.spec.width,
                                        self.spec.depth)
            tree = build_tree(req.tokens[-1], chains, T,
                              max_depth=self.spec.depth)
            trees[s] = tree
            tree_rows.append(len(slots_of))
            parents.append(tree.parents)
            slots_of.append(s)
            drafted = tree.n_nodes - 1
            self.spec_drafted += drafted
            req.spec_drafted += drafted
            tick_drafted += drafted
        if sp:
            sp.set(live=len(live), width=T, drafted=tick_drafted)
        sp.__exit__(None, None, None)
        anc = (ancestor_masks(np.stack(parents)) if parents
               else np.zeros((0, T, T), bool))
        pos = np.array([self._active[s].pos if self._active[s] else 0
                        for s in range(self.slots)], np.int32)

        # items: a tree (q_len = its real node count — padding nodes
        # are skipped work whose writes land in the null page) or one
        # committed-token row for a sampled slot. Mid-prefill slots
        # pack no item, so their partially filled pages are never a
        # write target
        items = []
        ti = iter(range(len(tree_rows)))
        for s in slots_of:
            req = self._active[s]
            if s in trees:
                k = next(ti)
                tree = trees[s]
                items.append((s, req.pos,
                              tree.tokens[:tree.n_nodes],
                              tree.depths, anc[k]))
            else:
                items.append((s, req.pos, [req.tokens[-1]],
                              None, None))
        sp = obs.span("verify").__enter__()
        if sp:
            sp.set(live=len(live), width=T,
                   pages_in_use=self.pool.pages_in_use)
        probs, padded, total = self._launch(items, T, tr, ntr)
        self._g_waste.set(padded / total if total else 0.0)
        if sp:
            sp.set(padded_rows=padded, total_rows=total)
        for s in self._admit_order:
            if self._mid_prefill(s):
                self._active[s].decode_overlap_ticks += 1

        # accept: greedy argmax walk. Both reductions run ON DEVICE —
        # per-node argmaxes for the walk and the root rows' _pick for
        # temperature>0 slots (one rng split per tick, same
        # discipline as the non-speculative servers) — so only
        # (items, max_nodes) + (slots,) ints cross to the host, never
        # the (items, max_nodes, vocab) probs. The root rows scatter
        # back to slot order on device so the shared slot-shaped
        # _pick program serves packed launches of any size
        temps = np.array(
            [self._active[s].temperature if self._active[s] else 0.0
             for s in range(self.slots)], np.float32)
        self._rng, sub = jax.random.split(self._rng)
        idx = jnp.asarray(np.array(slots_of, np.int32))
        root = jnp.zeros((self.slots, probs.shape[-1]), probs.dtype)
        root = root.at[idx].set(probs[:, 0, :])  # fflint: cow-ok (fresh logits scatter buffer, never a pool page)
        preds = np.asarray(jnp.argmax(probs, axis=-1))  # (items, T)
        temps_d = jnp.asarray(temps)
        sampled = np.asarray(self._pick(root, temps_d, sub))
        self._synced = self.launches    # the verify launch is done
        sp.__exit__(None, None, None)  # verify: closes at host sync
        item_of = {s: i for i, s in enumerate(slots_of)}
        plans = {}
        for s in live:
            req = self._active[s]
            if req.temperature > 0.0:
                plans[s] = ([0], [], int(sampled[s]))
            else:
                path, emitted = accept_greedy(trees[s],
                                              preds[item_of[s]])
                plans[s] = (path, emitted[:-1], emitted[-1])
        self._steps += 1
        self.spec_steps += 1

        # commit: accepted path rows -> contiguous committed rows
        # (unused entries self-copy; built before tables mutate)
        sp = obs.span("commit").__enter__()
        a0, e0 = self.spec_accepted, self.spec_emitted
        src = np.repeat(pos[:, None], C, axis=1)
        dst = src.copy()
        for s in live:
            req = self._active[s]
            path, verified, bonus = plans[s]
            emitted = verified + [int(bonus)]
            emitted = emitted[:req.max_new - len(req.tokens)]
            if self.eos_id is not None and self.eos_id in emitted:
                emitted = emitted[:emitted.index(self.eos_id) + 1]
            L = len(emitted)
            # accepted = verified draft tokens actually EMITTED (the
            # max_new/EOS cut above must not inflate acceptance)
            accepted = min(len(verified), L)
            self.spec_accepted += accepted
            req.spec_accepted += accepted
            src[s, :L] = req.pos + np.asarray(path[:L], np.int32)
            dst[s, :L] = req.pos + np.arange(L, dtype=np.int32)
            req.pos += L
            req.tokens.extend(int(t) for t in emitted)
            self._tokens[s] = emitted[-1]
            req.spec_steps += 1
            req.spec_emitted += L
            self.spec_emitted += L
        self._caches = self._commit(self._caches,
                                    self._tables_device(),
                                    jnp.asarray(src),
                                    jnp.asarray(dst))
        if self._caches_ref is not None:
            # quant-debug shadow (scheduler._launch) must see the
            # same accepted-row commit; the fp pool takes the plain
            # copy path inside the same jitted program
            self._caches_ref = self._commit(
                self._caches_ref, self._tables_device(),
                jnp.asarray(src), jnp.asarray(dst))
        for s in live:
            # publish AFTER the commit: only rows below the advanced
            # write head are committed K/V — tree scratch rows past
            # it must never reach the prefix cache (the tree-slack
            # pages stay private until pos actually crosses them)
            self._publish_prefix(self._active[s], self._active[s].pos)
            self._finish_if_done(s)
        emitted = self.spec_emitted - e0
        if sp:
            sp.set(emitted=emitted,
                   accepted=self.spec_accepted - a0)
        sp.__exit__(None, None, None)
        dt = time.monotonic() - t0
        self._h_tick.observe(dt)
        self._h_tokens.observe(emitted)
        if tick_drafted:
            self._h_accept.observe((self.spec_accepted - a0)
                                   / tick_drafted)
        led = obs.ledger()
        if led is not None:
            led.record("verify", dt, batch=len(live), width=T)
