"""Mellum-2's block (sliding-window layers beside full ones, two ropes, a
dropless expert share without a shared expert) at a tiny size, float32,
seeded random weights: the program, dense and through its TWO CLASSES of
pages, against the plain reference of benchmark/reference/mellum2.py.

Sizes: hidden 128, 4 heads on 2 kv heads of 128, window 16, pages of 8,
(sliding, sliding, full, full) a period, 8 experts top 2 of width 64; the
full layers' YaRN has original_max_position_embeddings 16 and factor 4,
so its ramp is live inside 40 positions.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mellum2 as fam
from benchmark.reference import mellum2 as ref
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.models.mellum2 import Mellum2Config, build_mellum2

VOCAB = 96
WINDOW = 16
PAGE = 8
KINDS = ["sliding_attention", "sliding_attention", "full_attention",
         "full_attention"]


def config(held=(0, 8), periods=1):
    """A configuration file's keys, at the tiny size."""
    n = 4 * periods
    return {
        "family": "mellum2", "hidden_size": 128, "num_hidden_layers": n,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
        "layer_types": KINDS * periods, "mlp_layer_types": ["sparse"] * n,
        "sliding_window": WINDOW, "use_sliding_window": True,
        "num_experts": held[1] - held[0], "num_experts_per_tok": 2,
        "moe_intermediate_size": 64, "norm_topk_prob": True,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        "tie_word_embeddings": False, "torch_dtype": "float32",
        "experts_held": list(held), "published": {"num_experts": 8},
    }


def build(cfg, seed=5, seq_len=8):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_mellum2(ff, fam.program_config(cfg), batch_size=1,
                  seq_len=seq_len, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def reference_logp(ff, cfg, ids):
    w = fam.reference_weights(ff._params[0], cfg)
    return np.asarray(jax.nn.log_softmax(
        fam.reference_logits(cfg)(w, jnp.asarray(ids))), np.float64)


@pytest.fixture(scope="module")
def tiny():
    cfg = config()
    return cfg, build(cfg)


# float32 on the CPU throughout; program and reference order their sums
# differently (online against whole softmax, grouped against dense
# experts): log-probabilities agree to a few float32 ulps of the logits
TOL = 1e-4


def test_the_builder_reads_layer_types():
    """Two periods: windows and ropes by `layer_types`, an expert share a
    block with no shared expert, heads of 128 on a hidden of 128."""
    from flexflow_tpu.runtime.executor import node_key

    cfg = config(held=(2, 6), periods=2)
    ff = build(cfg)
    attn = [n for n in ff.executor.topo
            if n.op_type == OpType.MULTIHEAD_ATTENTION]
    assert [n.attrs.window for n in attn] == [16, 16, None, None] * 2
    assert [n.attrs.rope_scaling is None for n in attn] == [
        True, True, False, False] * 2
    assert attn[2].attrs.rope_scaling == (4.0, 16, 32.0, 1.0,
                                          1.1386294361119891)
    assert {(n.attrs.kdim, n.attrs.num_kv) for n in attn} == {(128, 2)}
    moe = [n.attrs for n in ff.executor.topo
           if n.op_type == OpType.EXPERT_SHARE]
    assert len(moe) == 8 and {(a.n_experts, a.k, a.held, a.shared_hidden)
                              for a in moe} == {(8, 2, (2, 6), 0)}
    classes = ff.executor.page_classes()
    assert [classes[node_key(n)] for n in attn] == [1, 1, 0, 0] * 2
    assert ff.executor.window_rows() == 16
    with pytest.raises(ValueError, match="layer_types"):
        build_mellum2(FFModel(FFConfig(batch_size=1)), Mellum2Config(
            layer_types=("chunked_attention",)), batch_size=1, seq_len=8)


def test_dense_forward_equals_the_reference():
    """(a) compile()'s forward over 48 positions, three windows long: the
    window mask, both ropes and the expert share against the reference."""
    cfg = config()
    ff = build(cfg, seq_len=48)
    ids = np.random.default_rng(3).integers(0, VOCAB, 48).astype(np.int32)
    probs = ff.executor.forward_fn()(*ff._params, jnp.asarray(ids[None]))
    got = np.log(np.asarray(probs[0], np.float64))
    np.testing.assert_allclose(got, reference_logp(ff, cfg, ids), atol=TOL,
                               rtol=0)


def test_generate_through_the_dense_cache_keeps_the_window(tiny):
    """The dense KV cache path (`ff.generate`) masks by the window too."""
    cfg, ff = tiny
    ids = np.random.default_rng(4).integers(0, VOCAB, 30).astype(np.int32)
    out = np.asarray(ff.generate(ids[None], max_new_tokens=6))[0]
    lp = reference_logp(ff, cfg, out)
    np.testing.assert_array_equal(lp[29:35].argmax(-1), out[30:36])


def _served_logp(ff, prompts, new_tokens, **server):
    """Every request through a paged server; returns per request its
    tokens and the log-probabilities of every row its launches computed
    (a row an item of a launch), with the invariants of both classes of
    pages checked after every launch, and {page: requests that wrote it}
    of the window class."""
    kw = dict(paged=True, slots=2, max_len=96, page_size=PAGE,
              prefill_chunk=16, prefix_cache=False)
    kw.update(server)
    srv = ff.serve_generation(**kw)
    launches, writers = [], {}
    real = srv._launch

    def launch(items, window, tr, ntr):
        for s, pos, toks, _d, _a in items:
            if not len(toks):
                continue        # an idle slot's entry of a decode launch
            rid = srv._active[s].seq
            for b in {(pos + i) // PAGE for i in range(len(toks))}:
                writers.setdefault(int(srv._tables_w[s, b]), set()).add(rid)
        out = real(items, window, tr, ntr)
        launches.append(([(srv._active[s].seq if len(toks) else None,
                           pos, len(toks))
                          for s, pos, toks, _d, _a in items],
                         np.asarray(out[0], np.float64)))
        srv._check_invariants()
        return out

    srv._launch = launch
    try:
        futs = [srv.submit(p, new_tokens) for p in prompts]
        toks = [np.asarray(f.result()) for f in futs]
    finally:
        srv.stop()
    got = []
    for rid, (p, t) in enumerate(zip(prompts, toks), start=1):
        lp = np.full((len(p) + len(t), ff_vocab(ff)), np.nan)
        for items, probs in launches:
            for i, (r, pos, n) in enumerate(items):
                if r == rid:
                    lp[pos:pos + n] = np.log(probs[i, :n])
        got.append(lp)
    return srv, toks, got, writers


def ff_vocab(ff):
    return ff.executor.sink.outputs[0].dims[-1].size


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_prefill_and_decode_through_two_classes_equal_the_reference(
        path, monkeypatch):
    """(b) chunked prefill, then decode, of four requests through two
    slots and a window class of 10 pages: a prompt shorter than the
    window, prompts longer than it, and enough of them that a page one
    request released is written again by another. LOGITS of every row
    the server computed against the reference's one full forward."""
    cfg = config()
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    ff = build(cfg)     # its step functions trace under the flag
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (10, 40, 70, 33)]
    srv, toks, got, writers = _served_logp(ff, prompts, 6)
    for p, t, lp in zip(prompts, toks, got):
        seq = np.concatenate([p, t])
        want = reference_logp(ff, cfg, seq)
        rows = ~np.isnan(lp[:, 0])
        assert rows.sum() == len(seq) - 1       # all but the last token
        np.testing.assert_allclose(lp[rows], want[rows], atol=TOL, rtol=0)
        np.testing.assert_array_equal(
            want[len(p) - 1:len(seq) - 1].argmax(-1), t)
    m = srv.metrics()
    assert m["preemptions"] == 0
    win = m["page_classes"]["window"]
    assert win["released"] > 0 and win["pages_in_use"] == 0
    assert win["free_pages"] == 2 * (-(-(WINDOW + 16) // PAGE) + 1)
    assert m["page_classes"]["full"]["pages_in_use"] == 0
    # a released page was handed to another request and written again
    assert any(len(r) > 1 for page, r in writers.items() if page)


def test_window_pages_are_released_exactly_behind_the_window(tiny):
    """(c) after every launch a request holds the blocks from its next
    row's window to its last written row and no other: 70 + 6 rows leave
    at most window + chunk + a page in the window class while the full
    class keeps every row."""
    _cfg, ff = tiny
    srv = ff.serve_generation(paged=True, slots=1, max_len=96,
                              page_size=PAGE, prefill_chunk=16,
                              prefix_cache=False)
    seen = []
    real = srv._launch

    def launch(items, window, tr, ntr):
        out = real(items, window, tr, ntr)
        req = srv._active[0]
        seen.append((items[0][1], sorted(req.window_pages), len(req.pages)))
        return out

    srv._launch = launch
    try:
        ids = np.random.default_rng(2).integers(0, VOCAB, 70, dtype=np.int32)
        srv.submit(ids, 6).result()
    finally:
        srv.stop()
    assert srv._w_slot_pages == 5 and srv.pool_w.num_pages == 6
    for pos, blocks, full_pages in seen:
        # at the launch: from the window of its first row to its last row
        assert blocks[0] == max(pos - WINDOW + 1, 0) // PAGE
        assert blocks == list(range(blocks[0], blocks[-1] + 1))
        assert len(blocks) <= 5 and full_pages >= -(-70 // PAGE)
    # chunks of 16 start on a page's first row here: 15 rows of window
    # before them and 16 of their own are four pages, never the fifth
    assert max(len(b) for _p, b, _f in seen) == 4
    assert srv.window_pages_released == -(-76 // PAGE) - len(seen[-1][1])


def test_admission_checks_both_budgets(tiny):
    """(c) two slots, a full class with room for both requests and a
    window class with room for one: the second request waits for the
    window class's budget, then runs; nothing is preempted."""
    _cfg, ff = tiny
    srv = ff.serve_generation(paged=True, slots=2, max_len=96,
                              page_size=PAGE, prefill_chunk=16,
                              prefix_cache=False, num_pages_window=7)
    peak = []
    real = srv._launch

    def launch(items, window, tr, ntr):
        peak.append(len(srv._live()))
        return real(items, window, tr, ntr)

    srv._launch = launch
    try:
        rng = np.random.default_rng(6)
        futs = [srv.submit(rng.integers(0, VOCAB, 60, dtype=np.int32), 4)
                for _ in range(2)]
        for f in futs:
            assert len(f.result()) == 4
    finally:
        srv.stop()
    assert max(peak) == 1 and srv.metrics()["preemptions"] == 0
    with pytest.raises(ValueError, match="num_pages_window"):
        ff.serve_generation(paged=True, slots=2, max_len=96, page_size=PAGE,
                            prefill_chunk=16, prefix_cache=False,
                            num_pages_window=4).submit(np.zeros(
                                60, np.int32), 4)


def test_host_walks_equal_device_runs_in_both_classes(tiny,
                                                      walks_against_runs):
    """A chunk's pieces ride one walk in BOTH classes of tables: the
    host's count (`_walks`) against `ragged_runs` on the rows each class's
    layers are handed, over every launch of two requests, pages of the
    window class being released and reused meanwhile
    (tests/test_paged.py `test_host_walks_equal_device_runs` has the
    other kinds of launch)."""
    _cfg, ff = tiny
    srv = ff.serve_generation(paged=True, slots=2, max_len=96,
                              page_size=PAGE, prefill_chunk=24,
                              prefix_cache=False)
    rng = np.random.default_rng(8)

    def drive():
        futs = [srv.submit(rng.integers(0, VOCAB, n, dtype=np.int32), 6)
                for n in (70, 41)]
        for f in futs:
            assert len(f.result()) == 6

    try:
        seen = walks_against_runs(srv, drive)
    finally:
        srv.stop()
    assert all(tbl.ndim == 3 and len(tbl) == 2 for _r, tbl, *_ in seen)
    assert sum(int(r.sum()) for r, *_ in seen) >= 8
    assert srv.window_pages_released > 0


def test_preemption_requeue_and_defrag_act_on_both_classes(tiny):
    """(c) a full class too small for two long requests: the younger is
    preempted, both its tables are freed, it is requeued and recomputed,
    and its tokens are the reference's; a defrag between launches
    compacts both classes and rewrites both tables."""
    cfg, ff = tiny
    srv = ff.serve_generation(paged=True, slots=2, max_len=96,
                              page_size=PAGE, prefill_chunk=16,
                              prefix_cache=False, num_pages=14)
    real = srv._launch
    n = [0]

    def launch(items, window, tr, ntr):
        n[0] += 1
        if n[0] % 3 == 0:
            srv.request_defrag()
        out = real(items, window, tr, ntr)
        srv._check_invariants()
        return out

    srv._launch = launch
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, VOCAB, k, dtype=np.int32) for k in (44, 46)]
    try:
        futs = [srv.submit(p, 20) for p in prompts]
        toks = [np.asarray(f.result()) for f in futs]
    finally:
        srv.stop()
    m = srv.metrics()
    assert m["preemptions"] >= 1 and m["defrags"] >= 1
    assert m["page_classes"]["window"]["pages_in_use"] == 0
    for p, t in zip(prompts, toks):
        seq = np.concatenate([p, t])
        want = reference_logp(ff, cfg, seq)
        np.testing.assert_array_equal(
            want[len(p) - 1:len(seq) - 1].argmax(-1), t)


def test_window_class_invariant_names_what_broke():
    """(c) the catalog's window-class entry on hand-made tables."""
    from flexflow_tpu.analysis import pool_invariants as inv

    tables = np.zeros((3, 8), np.int32)
    tables[0, 2:5] = [4, 5, 6]
    rows = {0: ({2: 4, 3: 5, 4: 6}, 33)}    # next row 33: window from 18
    assert inv.check_window_class(tables, rows, 16, 8) == []
    assert inv.by_name("window-class").scope == "window"
    tables[1, 0] = 4                        # a page in two tables
    bad = inv.check_window_class(tables, {**rows, 1: ({0: 4}, 3)}, 16, 8)
    assert any("slots 0 and 1" in b for b in bad)
    tables[1] = 0
    tables[0, 2] = 0                        # released inside the window
    bad = inv.check_window_class(tables, {0: ({3: 5, 4: 6}, 33)}, 16, 8)
    assert any("blocks [2]" in b and "released" in b for b in bad)
    tables[2, 1] = 9                        # an idle slot's table
    assert any("slots [2]" in b for b in inv.check_window_class(
        tables, {0: ({3: 5, 4: 6}, 40)}, 16, 8))


# --- (d) the kernel against the gather fallback ---------------------------


def _kernel_case(window, pos, q_lens, S, cols=40, seed=0):
    from flexflow_tpu.paged.attention import (
        ragged_flash_attention,
        ragged_gather_attention,
    )

    rng = np.random.default_rng(seed)
    hkv, rep, d, n = 2, 2, 128, 90
    kc = jnp.asarray(rng.normal(size=(n, PAGE, hkv * d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(n, PAGE, hkv * d)), jnp.float32)
    B = len(pos)
    tbl = np.zeros((B, cols), np.int32)
    for b in range(B):
        tbl[b] = rng.permutation(np.arange(1, n))[:cols]
        if window is not None:      # what the window class has released
            tbl[b, :max(pos[b] - window + 1, 0) // PAGE] = 0
    q = jnp.asarray(rng.normal(size=(B, S, hkv * rep, d)), jnp.float32)
    anc = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
    args = (q, kc, vc, jnp.asarray(tbl), jnp.asarray(pos, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), anc)
    got = ragged_flash_attention(*args, scale=0.09, interpret=True,
                                 window=window)
    want = ragged_gather_attention(*args, scale=0.09, window=window)
    live = np.arange(S)[None, :] < np.asarray(q_lens)[:, None]
    return np.asarray(got)[live], np.asarray(want)[live]


@pytest.mark.parametrize("window,S", [
    (16, 1), (16, 8), (5, 8), (24, 3), (128, 8), (130, 8), (None, 8)])
def test_kernel_equals_gather_at_window_and_block_edges(window, S):
    """(d) a table of 40 pages of 8 rows is walked 16 pages (128 keys) a
    block: items whose window starts in block 0, exactly at a block's
    first key (pos - window + 1 = 128), one key before and after it, in
    the last block, padded items, and a chunk that straddles two blocks
    with a window wider than one."""
    w = window or 0
    pos = [0, 3, w + 127, w + 128, w + 126, 255, 300, 17]
    q_lens = [S, S, S, max(S - 2, 1), S, S, 0, S]
    got, want = _kernel_case(window, pos, q_lens, S)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_visibility_mask_counts_the_window_rows():
    from flexflow_tpu.paged.attention import ragged_visibility_mask

    tbl = jnp.ones((1, 8), jnp.int32)
    anc = jnp.tril(jnp.ones((4, 4), bool))[None]
    m = ragged_visibility_mask(tbl, jnp.asarray([30]), jnp.asarray([4]),
                               anc, 8, window=16)
    # row t at position 30 + t sees itself and the 15 rows before it
    np.testing.assert_array_equal(np.asarray(m[0]).sum(-1), [16] * 4)
    assert bool(m[0, 0, 15]) and not bool(m[0, 0, 14])
    assert bool(m[0, 3, 33]) and not bool(m[0, 3, 17])
    full = ragged_visibility_mask(tbl, jnp.asarray([30]), jnp.asarray([4]),
                                  anc, 8)
    np.testing.assert_array_equal(np.asarray(full[0]).sum(-1),
                                  [31, 32, 33, 34])


# --- (e) the expert shares -------------------------------------------------


def test_four_shares_of_an_eighth_add_up_to_the_uncut_layer():
    """(e) the guide's share test at shared_hidden 0: the parts of shares
    [0,2) [2,4) [4,6) [6,8) of a top-2 layer (8 a token at the published
    size) are the uncut reference layer's expert block."""
    from flexflow_tpu.ops.expert_share import expert_share
    from flexflow_tpu.runtime.executor import node_key

    whole_cfg = config()
    whole = build(whole_cfg)
    node = next(n for n in whole.executor.topo
                if n.op_type == OpType.EXPERT_SHARE)
    assert node.attrs.shared_hidden == 0
    params = whole._params[0][node_key(node)]
    assert not any(k.startswith("shared") for k in params)
    h = jax.random.normal(jax.random.key(3), (23, 128), jnp.float32)
    lyr = fam.reference_weights(whole._params[0], whole_cfg).layers[0]
    r = ref.lower_precision(None)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lyr, fam.reference_arch(whole_cfg), r)
    total = jnp.zeros_like(h)
    for lo in range(0, 8, 2):
        attrs = dataclasses.replace(node.attrs, held_lo=lo, held_hi=lo + 2)
        part = {k: (v[lo:lo + 2] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in params.items()}
        y, stats = expert_share(attrs, h, part)
        assert int(stats[2]) == 2
        total = total + y
        share_cfg = config(held=(lo, lo + 2))
        with jax.default_matmul_precision("highest"):
            ref_part = ref._experts(
                h, lyr._replace(w_gate=part["w_gate"], w_up=part["w_up"],
                                w_down=part["w_down"]),
                fam.reference_arch(share_cfg), r)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=0)


# --- the server's refusals --------------------------------------------------


@pytest.mark.parametrize("option", [
    {"paged": False}, {"prefix_cache": True}, {"kv_dtype": "int8"},
    {"host_tier": 8}, {"kv_quant_canary": 2}, {"speculate": "spec"},
    {"search_budget": 2}])
def test_unsupported_serving_options_are_refused_by_name(tiny, option):
    """What is not built over two classes of pages raises at construction
    and names itself (prefix_cache is on by default: it has to be turned
    off by name)."""
    _cfg, ff = tiny
    kw = dict(paged=True, slots=2, max_len=64, page_size=PAGE,
              prefix_cache=False)
    kw.update(option)
    if "speculate" in option:
        from flexflow_tpu.spec import SpecConfig

        kw["speculate"] = SpecConfig()
    name = next(iter(option))
    with pytest.raises(ValueError, match=name):
        ff.serve_generation(**kw)


def test_nothing_compiles_after_warm_launch_shapes(tiny):
    """After warm_launch_shapes() a tick of the two-class server compiles
    nothing, jitted or eager, and every pool leaf of both classes came
    back in the buffer it went in with."""
    import jax.monitoring

    _cfg, ff = tiny
    server = ff.serve_generation(paged=True, slots=2, max_len=48,
                                 page_size=PAGE, prefill_chunk=16,
                                 prefix_cache=False)
    server.warm_launch_shapes()
    passed, in_place = zip(*server._pool_alias.values())
    assert set(passed) == {8} and passed == in_place   # 4 nodes x K, V
    seen = []
    armed = [True]

    def listener(name, _secs, **_kw):
        if armed[0] and name == "/jax/core/compile/backend_compile_duration":
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        rng = np.random.default_rng(0)
        futs = [server.submit(rng.integers(0, VOCAB, n, dtype=np.int32), 4)
                for n in (3, 9, 14, 17, 22, 25, 31, 36, 44)]
        for f in futs:
            f.result()
    finally:
        armed[0] = False
        server.stop()
    assert not seen
    assert server.metrics()["compile"]["steady_state_recompiles"] == 0


def test_launch_spans_count_each_class(tiny):
    """The traced launch's counters, by class: one slot's 16-row chunk at
    position 32 of a 70-token prompt."""
    from flexflow_tpu import obs

    _cfg, ff = tiny
    rec = obs.enable()
    try:
        srv = ff.serve_generation(paged=True, slots=1, max_len=96,
                                  page_size=PAGE, prefill_chunk=16,
                                  prefix_cache=False)
        try:
            ids = np.random.default_rng(2).integers(0, VOCAB, 70,
                                                    dtype=np.int32)
            srv.submit(ids, 3).result()
        finally:
            srv.stop()
    finally:
        obs.disable()
    spans = [ev[4] for ev in rec.events if ev[0] == "launch_dispatch"]
    third = spans[2]        # rows 32..47: two 8-row pieces
    assert third["kv_pages_full"] == 6 == third["window_pages_walked_if_full"]
    assert third["kv_pages_window"] == 6 - (32 - 15) // PAGE
    assert third["qk_pairs_full"] == 16 * 32 + 16 * 17 // 2
    assert third["qk_pairs_window"] == 16 * 16
    # two window nodes and two full ones, K and V, float32 pages of 8 rows
    page = 2 * 2 * PAGE * 256 * 4
    assert third["pool_bytes_if_one_class"] == 9 * 2 * page
    assert third["pool_bytes_resident"] == (9 + 4) * page
    assert sum(s["window_pages_released"] for s in spans) > 0


# --- (f) a graph without window layers is what it was ----------------------

# sha256 of the StableHLO text (no source locations in it) of the PARENT's
# ragged step program (commit 5ec2c2d, PR 34) for the Mistral-7B tiny preset
# of tests/benchmark/perfbench_helpers.py, lowered as a server launches it:
# the (2, 1) decode launch and a (3, 8) packed launch, through the gather
# fallback and with the Pallas kernel interpreted (its body is then part of
# the text). Taken with this container's jax; another jax prints another
# text, so the test then only checks the argument list. The two "kernel"
# lines were taken again at PR 49, which rewrote the kernel's body (one walk
# a run); the "gather" lines, where the kernel is not in the text, are still
# PR 34's: nothing but the kernel moved.
PARENT_JAX = "0.9.0"
PARENT_STEP_SHA256 = {
    ("gather", 2, 1):
        "b812673a83170b849775b29058f7020e5410ea669f2dbbb9e407cd72a26d4f93",
    ("gather", 3, 8):
        "b7262c81116e4de601ea44ef57a53004100b609c445f0d47f057cb71966e6b5f",
    ("kernel", 2, 1):
        "1fd8a251b8757b6e6c22bed6a6e6063ce952914bc813d9e7d819c9afafd25cd1",
    ("kernel", 3, 8):
        "7f44c499c34fb8e766f3f8b8f440acb73872e346f980580a8d77cd68bae072b9",
}


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_a_graph_without_window_layers_lowers_to_the_parents_step(
        path, monkeypatch):
    """(f) one class of pages, one (B, max_pages) table, the parent's
    argument list and, where the jax is the parent's, the parent's
    StableHLO to the byte: the Mistral-7B cells' programs did not change
    with what other graphs' layers can now do."""
    import hashlib

    from benchmark.families import mistral
    from flexflow_tpu.runtime.serving_weights import serving_params

    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    ff = mistral.build_server_model({
        "family": "mistral", "hidden_size": 256, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 128, "vocab_size": 512,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
        "sliding_window": None, "tie_word_embeddings": False}, 3)
    ex = ff.executor
    assert ex.page_classes() is None and ex.window_rows() == 0
    tr, ntr = serving_params(ex, ex.abstract_params())
    caches = ex.paged_kv_cache_specs(9, 16)
    for B, W in ((2, 1), (3, 8)):
        avals = ex.ragged_step_avals(B, W, 4)
        assert [(a.shape, a.dtype.name) for a in avals] == [
            ((B, 4), "int32"), ((B,), "int32"), ((B,), "int32"),
            ((B, W), "int32"), ((B, W, W), "bool"), ((B, W), "int32")]
        text = ex.ragged_step_fn().lower(tr, ntr, caches, *avals).as_text()
        if jax.__version__ == PARENT_JAX:
            assert hashlib.sha256(text.encode()).hexdigest() == (
                PARENT_STEP_SHA256[(path, B, W)])
    srv = ff.serve_generation(paged=True, slots=2, max_len=64, page_size=16)
    try:
        assert srv.pool_w is None and "page_classes" not in srv.metrics()
        assert srv._tables_device().shape == (2, 4)
    finally:
        srv.stop()
    with pytest.raises(ValueError, match="num_pages_window"):
        ff.serve_generation(paged=True, slots=2, max_len=64, page_size=16,
                            num_pages_window=5)
