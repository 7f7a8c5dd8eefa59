"""Ling-3.0-flash's block (delta-rule linear attention with a state a slot,
a gated latent layer on pages, a dense layer, then sigmoid group-routed
experts with a shared one) at a tiny size, float32, seeded random weights:
the program through its pages AND states against the plain reference of
benchmark/reference/ling3.py.

Sizes (`Ling3Config.tiny`): hidden 64, 3 layers dense-KDA / KDA / MLA, 4
heads of 16, latent 16 + rope 8, 2 groups of 4 experts, one group open,
2 a token + 1 shared, one group held where a share is tested.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import ling3 as fam
from benchmark.reference import ling3 as ref
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.models.ling3 import build_ling3
from flexflow_tpu.runtime.executor import node_key

VOCAB = 96
ROWS = 8        # a packed launch's window (PREFILL_WINDOW_ROWS)


def config(held=(0, 8)):
    """A configuration file's keys, at the tiny size: published layers
    0-2 of a period of three."""
    return {
        "family": "ling3", "hidden_size": 64, "num_hidden_layers": 3,
        "first_layer": 0, "first_k_dense_replace": 1,
        "intermediate_size": 96, "num_attention_heads": 4, "head_dim": 16,
        "layer_group_size": 3, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
        "linear_silu": True, "use_qk_norm": True, "q_lora_rank": None,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "rotary_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
        "num_experts": held[1] - held[0], "num_experts_per_tok": 2,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 32, "n_group": 2,
        "topk_group": 1, "score_function": "sigmoid",
        "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5,
        "gated_attention_proj_granularity_type": "head_wise",
        "expert_swiglu_limit_list": [0, 0, 0, 4],
        "share_expert_swiglu_limit_list": [0, 0, 0, 5],
        "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "torch_dtype": "float32",
        "experts_held": list(held),
        "published": {"num_experts": 8, "first_k_dense_replace": 1},
    }


def build(cfg, seed=5):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_ling3(ff, fam.program_config(cfg), batch_size=1, seq_len=8,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def reference_logp(ff, cfg, ids):
    w = fam.reference_weights(ff._params[0], cfg)
    return jax.nn.log_softmax(fam.reference_logits(cfg)(w, jnp.asarray(ids)))


class Launches:
    """The ragged step driven as the server drives it: `slots` states and
    page-table rows; a launch is a list of (slot, first row, tokens), each
    split into 8-row pieces that ride as consecutive items."""

    def __init__(self, ff, slots, max_rows, page_size=8):
        ex = ff.executor
        self.step, (self.tr, self.ntr) = ex.ragged_step_fn(), ff._params
        pages = -(-max_rows // page_size)
        self.caches = ex.init_paged_kv_cache(1 + slots * pages, page_size,
                                             slots=slots)
        self.tables = 1 + np.arange(slots * pages, dtype=np.int32).reshape(
            slots, pages)

    def __call__(self, work, window=ROWS, pads=()):
        """-> [log-probabilities (rows, V) of each entry of `work`].
        `pads` are extra (slot, first row) items WITHOUT rows, placed
        after the work."""
        items, owner = [], []
        for j, (slot, start, toks) in enumerate(work):
            for off in range(0, len(toks), window):
                items.append((slot, start + off, toks[off:off + window]))
                owner.append(j)
        items += [(s, p, []) for s, p in pads]
        B = len(items)
        ids = np.zeros((B, window), np.int32)
        for i, (_s, _p, t) in enumerate(items):
            ids[i, :len(t)] = t
        slot = np.array([s for s, _p, _t in items], np.int32)
        deps = jnp.broadcast_to(jnp.arange(window, dtype=jnp.int32),
                                (B, window))
        anc = jnp.broadcast_to(
            jnp.tril(jnp.ones((window, window), jnp.bool_)),
            (B, window, window))
        probs, self.caches = self.step(
            self.tr, self.ntr, self.caches, jnp.asarray(self.tables[slot]),
            jnp.asarray(np.array([p for _s, p, _t in items], np.int32)),
            jnp.asarray(np.array([len(t) for _s, _p, t in items], np.int32)),
            deps, anc, jnp.asarray(ids), state_slots=jnp.asarray(slot))
        self.caches.pop("__launch_stats__")
        logp = np.log(np.asarray(probs, np.float64))
        return [np.concatenate([logp[i, :len(items[i][2])]
                                for i in range(len(owner)) if owner[i] == j])
                for j in range(len(work))]

    def states(self):
        return {nk: {n: np.asarray(b) for n, b in bufs.items()}
                for nk, bufs in self.caches.items() if "s" in bufs}


def served_logp(ff, ids, cuts, slot=1, mate=None):
    """`ids` through pages and states: chunks ending at `cuts`, then a
    token a launch; `mate`, another sequence, rides every launch in
    slot 0 in front."""
    run = Launches(ff, 3, len(ids))
    out, start = [], 0
    bounds = list(cuts) + list(range(cuts[-1] + 1, len(ids) + 1))
    for end in bounds:
        work = [(slot, start, ids[start:end])]
        if mate is not None:
            work.insert(0, (0, start, mate[start:end]))
        window = ROWS if end - start > 1 else 1
        out.append(run(work, window=window)[-1])
        start = end
    return np.concatenate(out)


@pytest.fixture(scope="module")
def tiny():
    cfg = config()
    return cfg, build(cfg)


IDS = np.random.default_rng(11).integers(0, VOCAB, 44).astype(np.int32)
MATE = np.random.default_rng(12).integers(0, VOCAB, 44).astype(np.int32)

# float32 on the CPU throughout; program and reference order their sums
# differently (absorbed against naive attention, the state's read-out with
# the update multiplied out): log-probabilities of magnitude ~5 agree to a
# few float32 ulps of the logits, as in tests/test_mistral4.py
TOL = 1e-4


@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_prefill_and_decode_through_pages_and_states_equal_the_reference(
        tiny, path, monkeypatch):
    """Chunks of 19 and 13 rows (pieces of 8, 8, 3 and 8, 5) to position
    32, then token by token to 44, another sequence beside it in every
    launch, against the reference's one full forward."""
    cfg, ff = tiny
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
        ff = build(cfg)     # its step functions trace under the flag
    got = served_logp(ff, IDS, (19, 32), mate=MATE)
    want = np.asarray(reference_logp(ff, cfg, IDS), np.float64)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_prompt_cut_at_any_boundary_gives_the_same_logits(tiny):
    """One chunk of 40 rows against chunks cut at odd places, 1-row and
    2-row chunks among them (float32 reassociation only: the state
    crosses every cut)."""
    _cfg, ff = tiny
    whole = served_logp(ff, IDS[:40], (40,))
    for cuts in ((1, 3, 4, 12, 13, 29, 40), (8, 16, 24, 32, 40),
                 (5, 37, 40)):
        got = served_logp(ff, IDS[:40], cuts)
        np.testing.assert_allclose(got, whole, atol=2e-5, rtol=0)


def test_pad_rows_and_idle_slots_leave_states_untouched(tiny):
    """A launch with slot 1's chunk, items without rows that name slots 0
    and 2 (at row 0: what a decode launch says of an idle slot), and a
    3-row piece whose five pad rows follow: slots 0 and 2 keep their
    states to the bit, slot 1's is what the same rows alone leave."""
    _cfg, ff = tiny
    run = Launches(ff, 3, 44)
    run([(0, 0, MATE[:11]), (2, 0, MATE[5:21])])
    before = run.states()
    run([(1, 0, IDS[:11])], pads=[(0, 0), (2, 0)])
    after = run.states()
    alone = Launches(ff, 3, 44)
    alone([(1, 0, IDS[:11])])
    for nk, bufs in after.items():
        for name, b in bufs.items():
            np.testing.assert_array_equal(b[[0, 2]], before[nk][name][[0, 2]])
            np.testing.assert_array_equal(b[1], alone.states()[nk][name][1])
            assert np.abs(b[1]).max() > 0
    # and leading items without rows (they take the first live slot's run)
    lead = Launches(ff, 3, 44)
    items_first = [(1, 0, IDS[:11])]
    lead.caches = jax.tree.map(jnp.array, alone.caches)
    lead([(1, 11, IDS[11:19])], pads=[])
    ref_run = Launches(ff, 3, 44)
    ref_run(items_first)
    ref_run([(1, 11, IDS[11:19])], pads=[(1, 19), (2, 0)])
    for nk, bufs in lead.states().items():
        for name, b in bufs.items():
            np.testing.assert_array_equal(b[1], ref_run.states()[nk][name][1])


def checked(server):
    """Check the invariant catalog before every launch (the states'
    account then matches the requests': a launch advances one before the
    tick advances the other)."""
    launch = server._launch

    def wrapper(*a, **kw):
        server._check_invariants()
        return launch(*a, **kw)

    server._launch = wrapper
    return server


def test_server_slot_reuse_and_evict_then_resume(tiny):
    """Through `serve_generation(paged=True)`: two slots and a pool too
    small for two long requests, so the younger is evicted, its state
    dropped, and resumed by recomputing prompt + emitted tokens; the
    slots then serve further requests in turn. Every request's greedy
    tokens are the reference's argmax and the catalog holds at every
    launch."""
    cfg, ff = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (44, 46, 9, 21)]
    server = checked(ff.serve_generation(
        paged=True, slots=2, max_len=96, page_size=8, num_pages=14,
        prefill_chunk=16, prefix_cache=False))
    try:
        futs = [server.submit(p, 20) for p in prompts]
        toks = [np.asarray(f.result()) for f in futs]
    finally:
        server.stop()
    m = server.metrics()
    for p, t in zip(prompts, toks):
        seq = np.concatenate([p, t])
        want = reference_logp(ff, cfg, seq)
        np.testing.assert_array_equal(
            want[len(p) - 1:len(seq) - 1].argmax(-1), t)
    assert m["preemptions"] >= 1
    state = m["state"]
    assert state["layers"] == 2
    assert state["bytes_per_slot"] == 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert state["resumes_by_recompute"] == m["preemptions"]
    assert state["resets"] == len(prompts) + m["preemptions"]


def test_state_leaves_are_written_in_place_and_nothing_compiles(tiny):
    import jax.monitoring

    _cfg, ff = tiny
    server = ff.serve_generation(paged=True, slots=2, max_len=48,
                                 page_size=8, prefill_chunk=8,
                                 prefix_cache=False)
    server.warm_launch_shapes()
    # a chunk's launch has the full window and a multiple of `slots` items
    assert set(server._pool_alias) == {(2, 1), (2, 8)}
    passed, in_place = zip(*server._pool_alias.values())
    assert set(passed) == {5} and passed == in_place   # 1 pool + 2 x (s, conv)
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


def test_a_state_graphs_launch_shapes_are_multiples_of_its_slots():
    """The catalog of the benchmark's cell (8 slots, chunk 512): the
    decode launch and one shape a multiple of 8 items up to the worst
    split, 64 pieces and 7 riders, where a graph without state layers has
    one a (items, window) pair."""
    from flexflow_tpu.analysis.shapecheck import enumerate_catalog

    kw = dict(slots=8, max_len=33280, page_size=64, prefill_chunk=512)
    ragged = enumerate_catalog(**kw, item_bucket=8)["entries"]["ragged_step"]
    assert ragged["shapes"] == [[8, 1]] + [[b, 8] for b in range(8, 73, 8)]
    assert enumerate_catalog(**kw)["entries"]["ragged_step"]["count"] == 127
    # a chunk under the window: the window is the chunk
    small = enumerate_catalog(slots=2, max_len=64, page_size=8,
                              prefill_chunk=4, item_bucket=2)
    assert small["entries"]["ragged_step"]["shapes"] == [[2, 1], [2, 4]]
    assert small["config"]["item_bucket"] == 2
    assert "item_bucket" not in enumerate_catalog(**kw)["config"]


def test_launch_spans_count_the_states(tiny):
    from flexflow_tpu import obs

    _cfg, ff = tiny
    rec = obs.enable()
    try:
        srv = ff.serve_generation(paged=True, slots=2, max_len=64,
                                  page_size=8, prefill_chunk=16,
                                  prefix_cache=False)
        try:
            srv.submit(IDS[:37], 3).result()
        finally:
            srv.stop()
    finally:
        obs.disable()
    spans = [ev[4] for ev in rec.events if ev[0] == "launch_dispatch"]
    first, third = spans[0], spans[2]
    assert (first["state_slots"], first["kda_rows"],
            first["kda_pieces"]) == (1, 16, 2)
    assert (third["kda_rows"], third["kda_pieces"]) == (5, 1)
    # items of ONE live row, named by the op as the rows are (a decode
    # launch's one item is one; a piece of 16 or 5 rows is not)
    assert [sp["kda_one_row"] for sp in spans[:4]] == [0, 0, 0, 1]
    assert "ssd_one_row" not in first
    assert first["state_bytes_per_slot"] == 2 * (4096 + 2304)
    assert first["kv_bytes_per_token"] == 128 * 4      # one latent layer


# (slot, first row, live rows) an item. "mixed": three slots' runs (one
# fresh, one continued, one of a single decode row), items without rows in
# front, between and behind. "long": an EMPTY item in front of slot 3's run
# (it carries `start`: the state is still copied in), 17 full items, the
# run's partial last piece, a rider of slot 1, a filler.
LAYOUTS = {
    "mixed": (5, [(4, 0, 0), (4, 0, 8), (4, 8, 3), (2, 7, 1), (2, 0, 0),
                  (0, 40, 8), (1, 0, 0), (1, 0, 0)]),
    "long": (5, [(3, 0, 0)] + [(3, 64 + 8 * i, 8) for i in range(17)]
             + [(3, 200, 5), (1, 77, 1), (1, 0, 0)]),
}


def _decay(regime, key, shape):
    """Log-decays of a regime: "weak" is what the layer's gate gives on
    random weights, "strong" the strongest it admits (-5 on every live row
    and channel: G = -40 across an item), "mixed" a draw over both ends
    with whole channels and whole rows at either."""
    if regime == "strong":
        return jnp.full(shape, -5.0)
    if regime == "weak":
        return -5.0 * jax.nn.sigmoid(jax.random.normal(key, shape) - 2)
    k1, k2, k3 = jax.random.split(key, 3)
    a = -5.0 * jax.nn.sigmoid(4 * jax.random.normal(k1, shape))
    rows = jax.random.bernoulli(k2, 0.25, shape[:2] + (1, 1))
    chans = jax.random.bernoulli(k3, 0.25, (1, 1) + shape[2:])
    return jnp.where(rows, -5.0, jnp.where(chans, -1e-3, a))


@pytest.mark.parametrize("regime,layout", [
    ("weak", "mixed"), ("strong", "mixed"), ("mixed", "mixed"),
    ("weak", "long"), ("strong", "long"), ("mixed", "long")])
def test_kernel_interpreted_equals_its_oracle(regime, layout):
    """`kda_ragged_scan`, interpreted, against the scan over items and
    rows, over decay regimes and launch layouts."""
    from flexflow_tpu.ops import kda_attention as kda
    from flexflow_tpu.ops.pallas import kda_scan

    # 3 heads go one a grid step, 8 all in one (their products merged)
    H, d = (3 if layout == "mixed" else 8), 16
    N, items = LAYOUTS[layout]
    slots, pos, q_lens = (jnp.asarray(col, jnp.int32) for col in zip(*items))
    B = slots.shape[0]
    ks = jax.random.split(jax.random.key(0), 6)
    q, k, v = (jax.random.normal(ks[i], (B, ROWS, H, d)) for i in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = _decay(regime, ks[3], (B, ROWS, H, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, ROWS, H)))
    state = jax.random.normal(ks[5], (N, H, d, d))
    alive = jnp.arange(ROWS)[None, :] < q_lens[:, None]
    a = jnp.where(alive[:, :, None, None], a, 0.0)
    beta = jnp.where(alive[:, :, None], beta, 0.0)
    chain = kda.item_chain(slots, pos, q_lens)
    if layout == "mixed":
        assert np.asarray(chain[0]).tolist() == [4, 4, 4, 2, 2, 0, 0, 0]
    else:       # the empty item in front starts slot 3's run
        assert np.asarray(chain[1]).tolist()[:2] == [True, False]
        assert int(q_lens[0]) == 0
    want_o, want_s = kda.scan_items(q, k, v, a, beta, chain, state)

    def flat(t):
        return t.reshape(B, ROWS, H * d)

    got_o, got_s = kda_scan.kda_ragged_scan(
        flat(q), flat(k), flat(k * beta[..., None]), flat(v), flat(a),
        state, chain[0], chain[1].astype(jnp.int32),
        chain[2].astype(jnp.int32), q_lens, heads=H, interpret=True)
    live, rows = np.asarray(alive), np.asarray(q_lens)
    got_o = np.asarray(got_o).reshape(B, ROWS, H, d)
    got_s, state = np.asarray(got_s), np.asarray(state)
    assert np.isfinite(got_o).all() and np.isfinite(got_s).all()
    np.testing.assert_allclose(got_o[live], np.asarray(want_o)[live],
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, np.asarray(want_s), atol=2e-5, rtol=0)
    # an item without rows reads out zeros, whatever its rows held
    assert not got_o[rows == 0].any()
    # slots named by no live item: untouched to the bit
    named = set(np.asarray(chain[0])[rows > 0].tolist())
    idle = sorted(set(range(N)) - named)
    assert idle
    np.testing.assert_array_equal(got_s[idle], state[idle])
    # a slot whose run ran live rows has another state than before
    for slot in named:
        assert np.abs(got_s[slot] - state[slot]).max() > 0.1


def test_router_groups_bias_and_scaling_on_a_hand_built_case():
    """8 experts in 2 groups, 1 group open, 2 a token. Scores are set
    through the logits; the bias opens group 1 against the raw scores and
    picks expert 6 over expert 5 inside it; the weights are the UNBIASED
    scores of the chosen, normalised, times 2.5."""
    from flexflow_tpu.ops.attrs import ExpertShareAttrs
    from flexflow_tpu.ops.expert_share import route

    attrs = ExpertShareAttrs(8, 2, 32, norm_topk=True, routed_scale=2.5,
                             score="sigmoid", n_group=2, topk_group=1,
                             select_bias=True)
    s = np.array([0.9, 0.8, 0.1, 0.1, 0.7, 0.6, 0.5, 0.1], np.float64)
    logit = np.log(s / (1 - s))
    x = jnp.asarray([[1.0, 0.0]])
    router = jnp.asarray(np.stack([logit, np.zeros(8)]), jnp.float32)
    # no bias: group 0 (0.9 + 0.8 = 1.7 against 1.3) and its two largest
    ids, w = route(attrs, x, router, jnp.zeros((8,)))
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1]
    np.testing.assert_allclose(sorted(np.asarray(w)[0]),
                               [2.5 * 0.8 / 1.7, 2.5 * 0.9 / 1.7], rtol=1e-5)
    # a bias of + 0.3 on experts 4 and 6: group 1 scores 1.0 + 0.8 = 1.8,
    # and inside it 4 (1.0) and 6 (0.8) beat 5 (0.6)
    bias = jnp.asarray([0, 0, 0, 0, 0.3, 0, 0.3, 0], jnp.float32)
    ids, w = route(attrs, x, router, bias)
    assert sorted(np.asarray(ids)[0].tolist()) == [4, 6]
    order = np.argsort(np.asarray(ids)[0])
    np.testing.assert_allclose(np.asarray(w)[0][order],
                               [2.5 * 0.7 / 1.2, 2.5 * 0.5 / 1.2], rtol=1e-5)
    # the reference's router says the same
    arch = fam.reference_arch(config())
    moe = ref.Moe(router=router, bias=bias, **{
        k: None for k in ref.Moe._fields if k not in ("router", "bias")})
    r_ids, r_w = ref.route(x, moe, arch)
    assert sorted(np.asarray(r_ids)[0].tolist()) == [4, 6]
    np.testing.assert_allclose(np.sort(np.asarray(r_w)[0]),
                               np.sort(np.asarray(w)[0]), rtol=1e-6)


def test_the_chips_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of shares [0, 4) and
    [4, 8) (a router group a chip), with the shared expert counted once,
    are the uncut reference layer's expert block; the reference given a
    share computes that share."""
    from flexflow_tpu.ops.expert_share import expert_share

    whole_cfg = config()
    whole = build(whole_cfg)
    node = next(n for n in whole.executor.topo
                if n.op_type == OpType.EXPERT_SHARE)
    params = whole._params[0][node_key(node)]
    assert float(jnp.abs(params["bias"]).max()) > 0     # drawn, not zero
    h = jax.random.normal(jax.random.key(3), (29, 64), jnp.float32)
    lyr = fam.reference_weights(whole._params[0], whole_cfg).layers[1].mlp
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lyr, fam.reference_arch(whole_cfg))
        shared = ref._swiglu(h, lyr.shared_gate, lyr.shared_up,
                             lyr.shared_down)
    total = jnp.zeros_like(h)
    for lo in (0, 4):
        attrs = dataclasses.replace(node.attrs, held_lo=lo, held_hi=lo + 4)
        part = {k: (v[lo:lo + 4] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in params.items()}
        y, stats = expert_share(attrs, h, part)
        assert int(stats[2]) == 4
        total = total + (y - shared)
        with jax.default_matmul_precision("highest"):
            ref_part = ref._experts(
                h, lyr._replace(w_gate=part["w_gate"], w_up=part["w_up"],
                                w_down=part["w_down"]),
                fam.reference_arch(config(held=(lo, lo + 4))))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("option", [
    {"paged": False}, {"prefix_cache": True}, {"kv_dtype": "int8"},
    {"host_tier": 8}, {"kv_quant_canary": 2}, {"speculate": "spec"},
    {"search_budget": 2}, {"serve_strategy": {}}])
def test_unsupported_serving_options_are_refused_by_name(tiny, option):
    """What cannot ride on a recurrent state raises at construction and
    names itself (prefix_cache is on by default: it has to be turned off
    by name)."""
    _cfg, ff = tiny
    kw = dict(paged=True, slots=2, max_len=64, page_size=8,
              prefix_cache=False)
    kw.update(option)
    if "speculate" in option:
        from flexflow_tpu.spec import SpecConfig

        kw["speculate"] = SpecConfig()
    name = next(iter(option))
    with pytest.raises(ValueError, match=f"{name}.*state layers"):
        ff.serve_generation(**kw)


def test_the_dense_paths_refuse_or_equal_the_reference(tiny):
    """`forward_fn` (no cache) is the reference's forward; a dense decode
    cache does not exist for a state layer."""
    cfg, ff = tiny
    probs = ff.executor.forward_fn()(*ff._params, jnp.asarray(IDS[None]))
    want = np.asarray(reference_logp(ff, cfg, IDS))
    np.testing.assert_allclose(np.log(np.asarray(probs[0])), want,
                               atol=TOL, rtol=0)


def test_slot_state_invariant_names_what_broke():
    from flexflow_tpu.analysis import pool_invariants as inv

    assert inv.by_name("slot-state").scope == "state"
    ok = [(7, 16), (None, 0), (9, 0)]
    live = {0: (7, 16), 2: (9, 0)}
    assert inv.check_slot_state(ok, live, [(0, 0, 8), (0, 8, 8)]) == []
    # not zero at admission
    v = inv.check_slot_state([(7, 16), (None, 0), (9, 5)], live, [])
    assert len(v) == 1 and "slot 2" in v[0] and "zero at admission" in v[0]
    # the state of a slot whose request left, and a launch that names it
    v = inv.check_slot_state([(7, 16), (3, 4), (9, 0)], live, [(1, 4, 1)])
    assert any("request 3, which is not live" in m for m in v)
    # one request, two states
    v = inv.check_slot_state([(7, 16), (None, 0), (7, 16)],
                             {0: (7, 16), 2: (7, 16)}, [])
    assert any("owns the states of slots 0 and 2" in m for m in v)
    # a slot's items apart, or out of row order
    v = inv.check_slot_state(ok, live, [(0, 0, 8), (2, 0, 1), (0, 8, 8)])
    assert any("not consecutive" in m for m in v)
    v = inv.check_slot_state(ok, live, [(0, 8, 8), (0, 0, 8)])
    assert any("row order" in m for m in v)
