"""racecheck's dynamic arm (ISSUE 18): the bounded interleaving model
checker over the threaded serving protocols, plus the live-code
stress companion that ties the abstract models back to the real
DisaggPair and ServingAutopilot.

Contracts under test: every protocol model — prefill->decode handoff,
concurrent spill/fetch/admission against the bounded host tier,
drain-and-swap under live submits and the serving loop's one-deep launch
pipeline (ISSUE 37: a page named by a launch in flight is neither freed
nor moved, admission runs beside it) — is FULLY explored violation-free
at the default context-switch bound (the explored/distinct state counts
are pinned: a model edit that shrinks the space is as suspicious as one
that breaks an invariant); sleep-set pruning is sound (the pruned and
unpruned explorations reach the identical distinct-state set); each
seeded protocol mutation produces its named invariant violation with a
minimal trace that replays to the same violation from the initial state;
and the real threaded code the models abstract — DisaggPair under
overlapped submits, an autopilot hot-swap under live traffic — keeps the
page-pool invariant catalog green at every resume point.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.analysis import racecheck
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama

# ---------------------------------------------------------------------------
# abstract models: clean exploration, pruning soundness, mutations


# explored/distinct-state counts at the default bound, pinned: the
# protocols are small enough to enumerate exactly, so any drift means
# the model (or the explorer) changed semantics — re-derive by hand
# before updating
_CLEAN_SPACE = {
    "handoff": (53, 48),
    "swap": (149, 117),
    "tierpool": (16, 15),
    "launch_ahead": (236, 96),
}


def test_all_protocol_models_fully_explored_clean_at_default_bound():
    assert set(racecheck.PROTOCOLS) == set(_CLEAN_SPACE)
    for name, cls in sorted(racecheck.PROTOCOLS.items()):
        res = racecheck.explore_interleavings(cls)
        assert res.hits == [], (name, res.hits)
        assert not res.truncated, name
        assert res.bound == racecheck.DEFAULT_SWITCH_BOUND
        assert (res.explored, res.distinct) == _CLEAN_SPACE[name], \
            (name, res.explored, res.distinct)


def test_sleep_set_pruning_is_sound():
    """Soundness cross-check: with pruning disabled the explorer visits
    strictly more interleavings but the DISTINCT state set is identical
    — pruning skips redundant orderings, never reachable states."""
    for name, cls in sorted(racecheck.PROTOCOLS.items()):
        pruned = racecheck.explore_interleavings(cls)
        full = racecheck.explore_interleavings(cls, prune=False)
        assert full.explored >= pruned.explored, name
        assert full.distinct == pruned.distinct, \
            (name, full.distinct, pruned.distinct)
        assert full.hits == pruned.hits == [], name


@pytest.mark.parametrize("model,mutation,invariant", [
    ("handoff", "double_submit", "single-owner"),
    ("tierpool", "fetch_no_remove", "tier-partition"),
    ("swap", "unlocked_submit", "future-dropped"),
    ("swap", "no_safepoint_join", "swap-during-handoff"),
    ("launch_ahead", "read_before_fence", "dispatch-buffer-owner"),
    ("launch_ahead", "free_at_late_stop", "stale-page-table"),
    ("launch_ahead", "defrag_without_fence", "stale-page-table"),
])
def test_seeded_mutation_produces_named_minimal_counterexample(
        model, mutation, invariant):
    """Each seeded protocol defect trips exactly its invariant, the
    reported schedule is minimal by BFS order (no strict prefix of it
    violates), and replaying it from the initial state reproduces the
    violation — the trace is evidence, not a transcript."""
    cls = racecheck.PROTOCOLS[model]

    def factory():
        return cls(mutations=(mutation,))

    res = racecheck.explore_interleavings(factory)
    hits = {h[0] for h in res.hits}
    assert invariant in hits, (model, mutation, res.hits)
    _inv, msg, trace = next(h for h in res.hits if h[0] == invariant)
    assert invariant in racecheck.PROTOCOL_INVARIANTS
    replayed = racecheck.replay_interleaving(factory, trace)
    assert any(v.split(":")[0] == invariant for v in replayed), \
        (trace, replayed)
    # minimality: no strict prefix already violates
    for cut in range(len(trace)):
        assert not any(v.split(":")[0] == invariant for v in
                       racecheck.replay_interleaving(factory,
                                                     trace[:cut])
                       if not v.startswith("deadlock")), \
            (cut, trace)


def test_wider_bound_only_grows_the_explored_space():
    """Raising the context-switch bound is monotone: more interleavings
    and at least as many distinct states, still violation-free — the
    default bound is a budget choice, not a soundness cliff."""
    for name, cls in sorted(racecheck.PROTOCOLS.items()):
        lo = racecheck.explore_interleavings(cls, max_switches=4)
        hi = racecheck.explore_interleavings(cls, max_switches=12)
        assert hi.explored >= lo.explored, name
        assert hi.distinct >= lo.distinct, name
        assert lo.hits == hi.hits == [], name


# ---------------------------------------------------------------------------
# live-code stress companions: the real threads behind the models


def _causal_lm(seed=7):
    lcfg = LlamaConfig(vocab_size=512, dim=64, layers=2, heads=4,
                       kv_heads=2, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=seed))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


def test_disagg_pair_invariant_clean_at_every_resume_point():
    """The handoff + tierpool models' real counterpart: overlapped
    submits through a DisaggPair in consecutive waves, with BOTH pools'
    invariant catalogs asserted at every resume point (each wave's
    quiesce, before the next wave races in on the still-warm tier) —
    the live analogue of check() running on every explored state, at
    the granularity the live pools can be observed race-free."""
    from flexflow_tpu.disagg import DisaggPair

    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(18)
    prompts = [rs.randint(1, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 7, 6, 8, 6)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0]
            for p in prompts]
    pair = DisaggPair(ff, tier_pages=64, page_size=4, num_pages=24,
                      max_len=32, slots=2)
    checks = 0
    try:
        for wave in range(3):
            idx = [2 * wave, 2 * wave + 1]
            futs = [(i, pair.submit(prompts[i], max_new_tokens=5))
                    for i in idx]
            for i, f in futs:
                got = f.result(timeout=120)
                np.testing.assert_array_equal(
                    want[i], np.asarray(got), err_msg=f"request {i}")
            # resume point: this wave quiesced, tier still carries
            # whatever the handoffs left behind for the next wave
            pair.prefill.pool.check_invariants(owners={})
            pair.decode.pool.check_invariants(owners={})
            checks += 1
        assert checks == 3
        assert pair.handoffs == len(prompts)
    finally:
        pair.stop()


def test_autopilot_swap_invariant_clean_under_live_submits():
    """The swap model's real counterpart: a drain-and-swap cutover
    races live submits, and the serving pool's invariant catalog holds
    at every resume point during AND after the swap — no request is
    dropped (future-dropped), none is answered twice, and the carried
    requests land token-identical."""
    from flexflow_tpu.search.servesearch import ServeStrategy
    from flexflow_tpu.serving_autopilot import ServingAutopilot

    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(19)
    pool = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
            for n in (3, 5, 4)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in pool]
    ap = ServingAutopilot(ff,
                          ServeStrategy(page_size=8, prefill_chunk=32),
                          slots=2, max_len=32)
    try:
        alt = dataclasses.replace(ap.strategy, prefill_chunk=16)
        swap = {}
        worker = threading.Thread(
            target=lambda: swap.update(ap.swap_to(alt)))
        worker.start()
        futs = []
        i = 0
        while worker.is_alive():
            if sum(1 for _, f in futs if not f.done()) < 4:
                futs.append(
                    (i % 3, ap.submit(pool[i % 3], max_new_tokens=6)))
                i += 1
            else:
                time.sleep(0.02)
        worker.join()
        for k, f in futs:
            np.testing.assert_array_equal(
                want[k], np.asarray(f.result(timeout=300)))
        # resume point 1: cutover complete, carried requests resolved —
        # the adopted pool must be invariant-clean
        ap.server.pool.check_invariants(owners={})
        assert swap["to"] == alt.fingerprint()
        # resume point 2: post-swap traffic through the new server,
        # checked again at its quiesce
        for j, f in enumerate([ap.submit(pool[j % 3], max_new_tokens=6)
                               for j in range(3)]):
            np.testing.assert_array_equal(
                want[j % 3], np.asarray(f.result(timeout=300)))
        ap.server.pool.check_invariants(owners={})
        assert ap.metrics()["autopilot"]["swaps"] == 1
    finally:
        ap.stop()
