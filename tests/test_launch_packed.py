"""One upload a launch (docs/paged.md "The launch descriptor").

A paged server tells the device about a launch's items through ONE int32
array, uploaded once and taken apart inside the step program
(`Executor.ragged_step_fn`'s `packed`, `runtime.executor.launch_columns`).
These tests hold the packed form to the positional one, which direct
callers keep: the same probabilities and pools to the bit on the five graph
kinds the benchmark serves (a K/V pool, a latent pool, two classes of
pages, a state a slot, a sparse latent layer) at a decode launch, a chunk
and a mixed launch, with and without entries fed from the device; one
transfer a chain launch and three a tree launch, counted where they are
made; a launch's result cannot move with a table written after its
dispatch; nothing compiles after warm-up.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_scope_checks as scope_checks
import test_glm5 as glm5_tiny
import test_ling3 as ling3_tiny
import test_mellum2 as mellum2_tiny
import test_one_launch_iteration as one_launch
from flexflow_tpu import obs
from flexflow_tpu.runtime.executor import (
    LAUNCH_DSA_STATS,
    LAUNCH_STATS,
    launch_columns,
)
from flexflow_tpu.spec import SpecConfig

VOCAB = 96
PAGE = 8
SLOTS = 3
COLS = 4        # table columns: 32 rows a slot
FAMILIES = ("mistral-7b", "mistral-small-4", "mellum2", "ling-3-flash",
            "glm-5.3-flash")
# what a graph's server is built with beyond the defaults
SERVER_KW = {"mistral-7b": {}, "mistral-small-4": {},
             "mellum2": {"prefix_cache": False},
             "ling-3-flash": {"prefix_cache": False},
             "glm-5.3-flash": {"prefix_cache": False}}


@pytest.fixture(scope="module")
def graphs():
    return {"mistral-7b": one_launch._llama(),
            "mistral-small-4": one_launch._mistral4(),
            "mellum2": mellum2_tiny.build(mellum2_tiny.config()),
            "ling-3-flash": ling3_tiny.build(ling3_tiny.config()),
            "glm-5.3-flash": glm5_tiny.build(glm5_tiny.config())}


def _chain(B, W):
    return (jnp.asarray(np.tile(np.arange(W, dtype=np.int32), (B, 1))),
            jnp.asarray(np.tile(np.tril(np.ones((W, W), np.bool_)),
                                (B, 1, 1))))


class Step:
    """The ragged step over its own pool, driven in either form: a launch
    is a list of (slot, first row, token ids, fed from the device?)."""

    def __init__(self, ff):
        ex = self.ex = ff.executor
        self.params = ff.serving_params()
        self.classes = 1 if ex.page_classes() is None else 2
        self.stateful = bool(ex.state_layers())
        pages = 1 + SLOTS * COLS
        self.caches = ex.init_paged_kv_cache(
            pages, PAGE, slots=SLOTS,
            **({"num_pages_window": pages} if self.classes == 2 else {}))
        self.tables = 1 + np.arange(SLOTS * COLS, dtype=np.int32).reshape(
            SLOTS, COLS)
        self.newest = jax.device_put(
            jnp.asarray(np.array([71, 72, 73], np.int32)),
            ex.launch_placement())

    def __call__(self, items, window, form, feed):
        B = len(items)
        at, width = launch_columns(window, self.classes, table_cols=COLS)
        packed = np.zeros((B, width), np.int32)
        packed[:, at["feed"]] = -1
        for i, (slot, start, toks, fed) in enumerate(items):
            packed[i, at["pos"]], packed[i, at["q_lens"]] = start, len(toks)
            packed[i, at["slot"]] = slot
            if fed and feed:
                packed[i, at["feed"]] = slot
            else:
                packed[i, :len(toks)] = toks
            for cols_c in at["tables"]:
                packed[i, cols_c] = self.tables[slot]
        deps, anc = _chain(B, window)
        step, (tr, ntr) = self.ex.ragged_step_fn(), self.params
        if form == "packed":
            kw = {"packed": jnp.asarray(packed)}
            if feed:
                kw["feed"] = (None, self.newest)
            probs, self.caches = step(tr, ntr, self.caches, None, None,
                                      None, deps, anc, **kw)
        else:
            rows = self.tables[packed[:, at["slot"]]]
            kw = {}
            if feed:
                kw["feed"] = (jnp.asarray(packed[:, at["feed"]].copy()),
                              self.newest)
            if self.stateful:
                kw["state_slots"] = jnp.asarray(
                    packed[:, at["slot"]].copy())
            probs, self.caches = step(
                tr, ntr, self.caches,
                jnp.asarray(rows if self.classes == 1
                            else np.stack([rows, rows])),
                jnp.asarray(packed[:, at["pos"]].copy()),
                jnp.asarray(packed[:, at["q_lens"]].copy()), deps, anc,
                jnp.asarray(packed[:, at["ids"]].copy()), **kw)
        self.caches.pop(LAUNCH_STATS, None)
        self.caches.pop(LAUNCH_DSA_STATS, None)
        return np.asarray(probs)


RNG = np.random.default_rng(7)
HISTORY = {s: RNG.integers(1, VOCAB, 8 + s).astype(np.int32)
           for s in range(SLOTS)}
CHUNK = RNG.integers(1, VOCAB, 13).astype(np.int32)
# launches after a history of 8, 9 and 10 rows in slots 0, 1 and 2
SHAPES = {
    # one row a slot, the newest token of each
    "decode": (1, [(s, 8 + s, [70 + s + 1], True) for s in range(SLOTS)]),
    # a chunk of 13 rows as two 8-row pieces of one slot
    "chunk": (8, [(0, 8, CHUNK[:8], False), (0, 16, CHUNK[8:], False)]),
    # the chunk's pieces with two decode rows riding behind them
    "mixed": (8, [(0, 8, CHUNK[:8], False), (0, 16, CHUNK[8:], False),
                  (1, 9, [72], True), (2, 10, [73], True)]),
}


@pytest.mark.parametrize("feed", [False, True], ids=["host-ids", "feed"])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES)
def test_packed_step_is_the_positional_step(graphs, family, shape, feed):
    """The step fed the one packed array returns the positional form's
    probabilities and pools, bit for bit: the same rows, positions, table
    rows, slots and fed entries reach the same kernels."""
    window, items = SHAPES[shape]
    got = {}
    for form in ("positional", "packed"):
        run = Step(graphs[family])
        # the same history in both pools, written by the positional form
        run([(s, 0, HISTORY[s], False) for s in range(SLOTS)], 16,
            "positional", False)
        probs = run(items, window, form, feed)
        got[form] = (probs, [np.asarray(leaf) for leaf in
                             jax.tree.leaves(run.caches)])
    np.testing.assert_array_equal(got["packed"][0], got["positional"][0])
    assert len(got["packed"][1]) == len(got["positional"][1]) > 0
    for a, b in zip(got["packed"][1], got["positional"][1]):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(got["packed"][0]).all()


def test_the_fed_entries_read_the_device_vector(graphs):
    """With `feed`, an entry whose feed column names a slot takes its
    first id from `newest`, whatever the ids column holds; without the
    keyword the column is ignored."""
    run = Step(graphs["mistral-7b"])
    items = [(s, 0, [int(run.newest[s])], False) for s in range(SLOTS)]
    by_ids = run(items, 1, "packed", False)
    run = Step(graphs["mistral-7b"])
    fed = run([(s, 0, [5], True) for s in range(SLOTS)], 1, "packed", True)
    np.testing.assert_array_equal(fed, by_ids)


def _prompts(n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, k, dtype=np.int32) for k in n]


@pytest.mark.parametrize("launch", ["chain", "tree"])
def test_a_launch_counts_its_uploads(graphs, launch):
    """A chain launch makes ONE host-to-device transfer (its packed
    descriptor; depths and ancestors are the shape's cached pair, two
    transfers at a shape's first launch), a launch with a drafted tree
    three (the tree's depths and ancestors beside it): counted where they
    are made, on the `launch_h2d` span and in `metrics()`."""
    kw = {"speculate": SpecConfig(width=2, depth=2)} if launch == "tree" \
        else {}
    srv = graphs["mistral-7b"].serve_generation(
        paged=True, slots=SLOTS, max_len=64, page_size=PAGE,
        prefill_chunk=16, prefix_cache=False, **kw)
    trees = []
    real = srv._launch

    def watched(items, window, tr, ntr):
        trees.append(any(d is not None or a is not None
                         for *_, d, a in items))
        return real(items, window, tr, ntr)

    srv._launch = watched
    try:
        srv.warm_launch_shapes()
        # a repeated pattern, so the n-gram drafter has trees to offer
        warm = [np.tile(np.array([5, 6, 7, 8], np.int32), 6)]
        for p in warm + _prompts((19, 7)):
            srv.generate(p, max_new_tokens=6)      # every shape seen once
        before = srv.metrics()["launch_uploads"]
        del trees[:]
        rec = obs.enable()
        try:
            for p in warm + _prompts((19, 7)):
                srv.generate(p, max_new_tokens=6)
        finally:
            obs.disable()
        made = srv.metrics()["launch_uploads"] - before
    finally:
        srv.stop()
    spans = [ev[4] for ev in sorted(rec.events, key=lambda ev: ev[1])
             if ev[0] == "launch_h2d"]
    assert len(spans) == len(trees) > 4
    assert all(a["launches"] == 1 for a in spans)
    assert sum(a["uploads"] for a in spans) == made
    assert [a["uploads"] for a in spans] == [3 if t else 1 for t in trees]
    assert any(trees) == (launch == "tree")


@pytest.mark.parametrize("family", ["mistral-7b", "mellum2"])
def test_a_table_written_after_dispatch_does_not_reach_the_launch(graphs,
                                                                  family):
    """The aliasing case: launch N is in flight while the host writes
    `_tables` for N + 1, and the CPU backend aliases a numpy buffer it is
    handed. A launch carries its OWN copy of its items' rows, so nulling
    the tables right after the dispatch changes nothing it returns."""
    srv = graphs[family].serve_generation(
        paged=True, slots=SLOTS, max_len=32, page_size=PAGE,
        prefill_chunk=16, defer_start=True, **SERVER_KW[family])
    try:
        tr, ntr = srv._params
        tables = [srv._tables] + ([srv._tables_w] if srv._window else [])
        rows = 1 + np.arange(COLS, dtype=np.int32)

        def launch(start, toks, null_after):
            for t in tables:
                t[0] = rows
            probs, _pad, _total = srv._launch(
                [(0, start, list(toks), None, None)], 8, tr, ntr)
            if null_after:
                for t in tables:
                    t[0] = 0        # the host moves on: in place
            return np.asarray(probs)

        launch(0, CHUNK[:8], False)                     # a history
        kept = launch(8, CHUNK[8:], False)
        # the same rows again over the same history, the tables nulled
        # while the launch may still be in flight
        nulled = launch(8, CHUNK[8:], True)
        np.testing.assert_array_equal(nulled, kept)
        # and a launch that really walks the null page reads otherwise
        for t in tables:
            t[0] = 0
        probs, *_ = srv._launch([(0, 8, list(CHUNK[8:]), None, None)], 8,
                                tr, ntr)
        assert np.abs(np.asarray(probs) - kept).max() > 0
    finally:
        srv.stop()


@pytest.mark.parametrize("family", FAMILIES)
def test_warm_up_leaves_nothing_to_compile(graphs, family):
    """`warm_launch_shapes` calls every launch shape with the packed
    operand exactly as the server feeds it: a short served trace, chunks,
    riders and decode launches, compiles nothing."""
    srv = graphs[family].serve_generation(
        paged=True, slots=SLOTS, max_len=64, page_size=PAGE,
        prefill_chunk=16, defer_start=True, **SERVER_KW[family])
    try:
        catalog = srv.warm_launch_shapes()
        futs = [srv.submit(p, max_new_tokens=new) for p, new in
                zip(_prompts((3, 30, 22, 17, 9)), (6, 1, 5, 4, 3))]
        srv.start()
        for f in futs:
            assert len(f.result(timeout=600))
        m = srv.metrics()
    finally:
        srv.stop()
    assert m["compile"]["steady_state_recompiles"] == 0
    assert m["launch_uploads"] >= m["launches_dispatched"] > 5
    assert catalog["entries"]["ragged_step"]["count"] >= 3


# ---------------------------------------------------------------------------
# what the step's operations are FOR: a group and an attention part in every
# name stack (obs/scopes.py `classify_serving`), on the same five graphs


@pytest.mark.parametrize("launch", scope_checks.LAUNCHES)
@pytest.mark.parametrize("family", FAMILIES)
def test_every_heavy_instruction_of_the_step_has_a_group(graphs, family,
                                                         launch):
    seen = scope_checks.check_every_heavy_instruction_has_a_group(
        graphs[family], launch)
    want = {"mistral-7b": {"attn", "ffn", "head", "glue"},
            "mistral-small-4": {"attn", "experts", "head", "glue"},
            "mellum2": {"attn", "experts", "head", "glue"},
            "ling-3-flash": {"attn", "state", "ffn", "experts", "head",
                             "glue"},
            "glm-5.3-flash": {"attn", "state", "ffn", "experts", "head",
                              "glue"}}[family]
    assert {g for g, _n in seen if g} == want


@pytest.mark.parametrize("launch", scope_checks.LAUNCHES)
@pytest.mark.parametrize("family", FAMILIES)
def test_the_steps_scopes_change_nothing_but_names(graphs, family, launch,
                                                   monkeypatch):
    scope_checks.check_scopes_change_nothing_but_names(
        graphs[family], launch, monkeypatch)


def test_hloaudit_lists_a_modules_layout_operations_by_group(graphs):
    """ROADMAP S10's "name them", repeatable without a chip: the layout
    operations of a compiled launch shape with the group and the
    attention part they were made for, a compiler-made copy (no
    `op_name` of its own) charged to its operand's."""
    from flexflow_tpu.analysis import hloaudit
    from flexflow_tpu.obs import scopes

    txt = scope_checks.lower_step(graphs["mistral-7b"],
                                  "chunk").compile().as_text()
    rows = hloaudit.layout_operations(txt)
    assert rows == sorted(rows, key=lambda r: -r["bytes"]) and len(rows) > 4
    for r in rows:
        assert hloaudit.is_layout(r["name"], r["opcode"])
        assert not r["opcode"].endswith("-done")
        assert r["group"] in scopes.GROUPS, r
        assert r["bytes"] > 0 and "[" in r["result"]
        if r["group"] == scopes.ATTN:
            assert r["part"] in scopes.ATTN_PARTS, r
    # the gather path lays the gathered pages out for the scores: an
    # attention node's `attend`, as on the chip the kernel's operands
    assert {(r["group"], r["part"]) for r in rows} >= {
        ("attn", "attend"), ("attn", "qkv")}
    # nothing inside a fusion's body is listed as an operation
    names = [r["name"] for r in rows]
    assert len(names) == len(set(names))
