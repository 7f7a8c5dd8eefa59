"""Mistral-Small-4's block (latent attention, a dropless expert share with
a shared expert) at a tiny size, float32, seeded random weights: the
program through its page pool against the plain reference of
benchmark/reference/mistral4.py.

Sizes: hidden 64, 4 heads, ranks 32 / 16, nope 8 / rope 8 / v 16, 8 experts
top 2 + 1 shared, 2 layers, original_max_position_embeddings 16 with YaRN
factor 8, so the frequency blend and the position scale on q are both live
inside 40 positions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mistral4 as fam
from benchmark.reference import mistral4 as ref
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.mistral4 import build_mistral4

VOCAB = 96


def config(held=(0, 8), layers=2):
    """A configuration file's keys, at the tiny size."""
    return {
        "family": "mistral4", "hidden_size": 64, "num_hidden_layers": layers,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": held[1] - held[0], "n_shared_experts": 1,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "first_k_dense_replace": 0, "n_group": 1, "topk_group": 1,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "rope_interleave": True,
        "rope_parameters": {
            "beta_fast": 32, "beta_slow": 1, "factor": 8,
            "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 16, "rope_theta": 10000,
            "rope_type": "yarn", "type": "yarn"},
        "tie_word_embeddings": False, "torch_dtype": "float32",
        "experts_held": list(held), "published": {"n_routed_experts": 8},
    }


def build(cfg, seed=5):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_mistral4(ff, fam.program_config(cfg), batch_size=1, seq_len=8,
                   dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def reference_logp(ff, cfg, ids):
    w = fam.reference_weights(ff._params[0], cfg)
    return jax.nn.log_softmax(fam.reference_logits(cfg)(w, jnp.asarray(ids)))


def paged_logp(ff, ids, *, chunk=8, prefill=24, page_size=8, batch_mate=None):
    """log-probabilities of every position of `ids` through the page pool:
    `prefill` tokens in chunks of `chunk` rows, the rest one token a step.
    `batch_mate`, another sequence, rides every launch as a second entry
    with its own page-table row."""
    ex = ff.executor
    step = ex.ragged_step_fn()
    tr, ntr = ff._params
    n = len(ids)
    seqs = [np.asarray(ids)] + ([np.asarray(batch_mate)]
                                if batch_mate is not None else [])
    B = len(seqs)
    pages = -(-n // page_size)
    caches = ex.init_paged_kv_cache(1 + B * pages, page_size)
    tables = jnp.asarray(1 + np.arange(B * pages, dtype=np.int32)
                         .reshape(B, pages))
    out = []
    start = 0
    while start < n:
        w = chunk if start < prefill else 1
        w = min(w, n - start)
        tok = np.stack([s[start:start + w] for s in seqs]).astype(np.int32)
        deps = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32), (B, w))
        anc = jnp.broadcast_to(jnp.tril(jnp.ones((w, w), jnp.bool_)),
                               (B, w, w))
        probs, caches = step(tr, ntr, caches, tables,
                             jnp.full((B,), start, jnp.int32),
                             jnp.full((B,), w, jnp.int32), deps, anc,
                             jnp.asarray(tok))
        stats = caches.pop("__launch_stats__")
        assert stats.shape[1] == 4      # a row a layer: STATS
        out.append(np.log(np.asarray(probs[0], np.float64)))
        start += w
    return np.concatenate(out, axis=0)


@pytest.fixture(scope="module")
def tiny():
    cfg = config()
    return cfg, build(cfg)


IDS = np.random.default_rng(11).integers(0, VOCAB, 40).astype(np.int32)

# float32 on the CPU throughout; the program and the reference order
# their sums differently (absorbed against naive attention, grouped
# against dense experts, online against whole softmax): log-probabilities
# of magnitude ~5 agree to a few float32 ulps of the logits, 1e-4 leaves a
# decade of room and is three decades under what a bfloat16 matmul moves
TOL = 1e-4


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_paged_prefill_and_decode_equal_the_reference(tiny, path,
                                                      monkeypatch):
    """(a) chunks of 8 to position 24, then token by token to 40, through
    the latent page pool, against the reference's one full forward."""
    cfg, ff = tiny
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
        ff = build(cfg)     # its step functions trace under the flag
    got = paged_logp(ff, IDS)
    want = np.asarray(reference_logp(ff, cfg, IDS), np.float64)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_absorbed_equals_naive_attention(tiny):
    """(b) one attention node: the paged lowering's absorbed form over the
    pool against the dense lowering's naive form, same weights."""
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops import latent_attention as la
    from flexflow_tpu.ops.registry import LowerCtx
    from flexflow_tpu.runtime.executor import node_key

    _cfg, ff = tiny
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.LATENT_ATTENTION)
    params = ff._params[0][node_key(node)]
    x = jax.random.normal(jax.random.key(2), (1, 40, 64), jnp.float32)
    naive = la.naive_attention(node.attrs, x, params)
    pool = jnp.zeros((7, 8, 128), jnp.float32)
    ctx = LowerCtx(training=False, kv_cache={"c": pool},
                   cache_position=jnp.zeros((1,), jnp.int32),
                   page_tables=jnp.arange(1, 6, dtype=jnp.int32)[None],
                   ragged_q_lens=jnp.full((1,), 40, jnp.int32),
                   ragged_depths=jnp.arange(40, dtype=jnp.int32)[None],
                   ragged_anc=jnp.tril(jnp.ones((40, 40), jnp.bool_))[None])
    absorbed, pools, _stats = la.paged_attention(node.attrs, x, params, ctx)
    pool = pools["c"]
    # the same products in another order: float32 rounding only
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(naive),
                               atol=2e-5, rtol=0)
    # the pool got rows of latent_width values and zero pad lanes
    assert float(jnp.abs(pool[1:6, :, :24]).max()) > 0
    assert float(jnp.abs(pool[:, :, 24:]).max()) == 0


def test_shares_add_up_to_the_uncut_layer():
    """(c) the guide's share test: the routed parts of shares [0,2) [2,4)
    [4,6) [6,8), with the shared expert counted once, are the uncut
    reference layer's expert block."""
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.expert_share import expert_share
    from flexflow_tpu.runtime.executor import node_key

    whole_cfg = config(layers=1)
    whole = build(whole_cfg)
    node = next(n for n in whole.executor.topo
                if n.op_type == OpType.EXPERT_SHARE)
    params = whole._params[0][node_key(node)]
    h = jax.random.normal(jax.random.key(3), (23, 64), jnp.float32)
    lyr = fam.reference_weights(whole._params[0], whole_cfg).layers[0]
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lyr, fam.reference_arch(whole_cfg))
        shared = ref._swiglu(h, lyr.shared_gate, lyr.shared_up,
                             lyr.shared_down)
    total = jnp.zeros_like(h)
    import dataclasses
    for lo in range(0, 8, 2):
        attrs = dataclasses.replace(node.attrs, held_lo=lo, held_hi=lo + 2)
        part = {k: (v[lo:lo + 2] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in params.items()}
        y, stats = expert_share(attrs, h, part)
        assert int(stats[2]) == 2
        total = total + (y - shared)
        # the reference given the same share computes the same part
        share_cfg = config(held=(lo, lo + 2), layers=1)
        with jax.default_matmul_precision("highest"):
            ref_part = ref._experts(
                h, lyr._replace(w_gate=part["w_gate"], w_up=part["w_up"],
                                w_down=part["w_down"]),
                fam.reference_arch(share_cfg))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_logits_do_not_depend_on_batch_mates(path, monkeypatch):
    """(d) a sequence alone and beside another in every launch: the same
    log-probabilities to the last bit of what the path computes row by
    row (no capacity, no drop). A share of the experts is held, so some
    assignments fall outside it."""
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    cfg = config(held=(2, 6))
    ff = build(cfg)
    mate = np.random.default_rng(12).integers(0, VOCAB, 40).astype(np.int32)
    alone = paged_logp(ff, IDS)
    paired = paged_logp(ff, IDS, batch_mate=mate)
    # XLA's CPU matmuls may block a (16, K) product differently from an
    # (8, K) one, and log() of a float32 probability near 1e-3 carries 1e-6:
    # float32 rounding is allowed, a dropped token (1e-1) is not
    np.testing.assert_allclose(paired, alone, atol=2e-5, rtol=0)
    want = np.asarray(reference_logp(ff, cfg, IDS), np.float64)
    np.testing.assert_allclose(alone, want, atol=TOL, rtol=0)


def test_pool_holds_one_entry_a_node(tiny):
    """(e) one "c" buffer a latent node, lanes = rank + rope to the lane
    tile, and the server's kv_bytes_per_token says so."""
    from flexflow_tpu.paged.latent import pool_lanes

    _cfg, ff = tiny
    specs = ff.executor.paged_kv_cache_specs(5, 8)
    assert len(specs) == 2
    lanes = pool_lanes(16 + 8)
    assert lanes == 128 and pool_lanes(256 + 64) == 384
    for bufs in specs.values():
        assert list(bufs) == ["c"]
        assert bufs["c"].shape == (5, 8, lanes)
    srv = ff.serve_generation(paged=True, slots=2, max_len=64, page_size=8,
                              prefill_chunk=16)
    try:
        toks = np.asarray(srv.submit(IDS[:30], 6).result())
        again = np.asarray(srv.submit(IDS[:30], 6).result())
    finally:
        srv.stop()
    m = srv.metrics()       # the expert counters are complete once stopped
    assert m["kv_bytes_per_token"] == 2 * lanes * 4     # layers x lanes x f32
    # the same prompt again is served from the prefix cache, same tokens
    np.testing.assert_array_equal(toks, again)
    assert m["prefix_cache"]["hit_tokens"] >= 24
    # every launch and layer counted: 2 experts a token, all 8 held
    assert m["experts_held"] % 8 == 0 and m["moe_assignments"] > 0
    assert 0 < m["experts_hit"] <= m["experts_held"]
    # greedy tokens are the reference's argmax continuation
    cfg = _cfg
    seq = np.concatenate([IDS[:30], toks])
    lp = np.asarray(reference_logp(ff, cfg, seq))
    np.testing.assert_array_equal(lp[29:35].argmax(-1), toks)


@pytest.mark.parametrize("option", [
    {"paged": False}, {"kv_dtype": "int8"}, {"host_tier": 8},
    {"kv_quant_canary": 2}, {"speculate": "spec"}, {"search_budget": 2}])
def test_unsupported_serving_options_are_refused_by_name(tiny, option):
    """(f) each option whose code reads per-head K/V pools raises at
    construction and names itself."""
    _cfg, ff = tiny
    kw = dict(paged=True, slots=2, max_len=64, page_size=8)
    kw.update(option)
    if "speculate" in option:
        from flexflow_tpu.spec import SpecConfig

        kw["speculate"] = SpecConfig()
    name = next(iter(option))
    with pytest.raises(ValueError, match=name):
        ff.serve_generation(**kw)


def test_weight_dtype_route(tiny):
    """FFConfig.weight_dtype stores the drawn weights at that dtype."""
    ff = FFModel(FFConfig(batch_size=1, seed=5, num_devices=1,
                          weight_dtype="bfloat16"))
    build_mistral4(ff, fam.program_config(config(layers=1)), batch_size=1,
                   seq_len=8, dtype=DataType.BFLOAT16)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    assert {leaf.dtype.name for leaf in jax.tree.leaves(ff._params[0])} == {
        "bfloat16"}


def _llama_tiny():
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=3, num_devices=1))
    build_llama(ff, LlamaConfig.tiny(vocab=VOCAB), seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


@pytest.mark.parametrize("model", ["mistral4", "llama"])
def test_nothing_compiles_after_warm_launch_shapes(tiny, model):
    """After warm_launch_shapes() a tick compiles nothing, jitted or
    eager: prompts that end in every piece of a packed chunk, decode
    ticks, first tokens (jax's own backend-compile events, which see the
    eager programs the compile tracker does not)."""
    import jax.monitoring

    ff = tiny[1] if model == "mistral4" else _llama_tiny()
    server = ff.serve_generation(paged=True, slots=2, max_len=40,
                                 page_size=8, prefill_chunk=16)
    server.warm_launch_shapes()
    seen = []
    armed = [True]

    def listener(name, _secs, **_kw):
        if armed[0] and name == "/jax/core/compile/backend_compile_duration":
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        rng = np.random.default_rng(0)
        futs = [server.submit(rng.integers(0, VOCAB, n, dtype=np.int32), 4)
                for n in (3, 9, 14, 17, 22, 25, 31, 36)]
        for f in futs:
            f.result()
    finally:
        armed[0] = False
        server.stop()
    assert not seen
    assert server.metrics()["compile"]["steady_state_recompiles"] == 0


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_pad_rows_reach_no_expert(tiny, path, monkeypatch):
    """A launch's pad rows (an idle entry with q_len 0, the tail of a
    short piece) are routed to no expert: the counters are those of the
    live rows alone, and the live rows' probabilities do not move."""
    cfg, ff = tiny
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
        ff = build(cfg)
    ex = ff.executor
    step = ex.ragged_step_fn()
    tr, ntr = ff._params
    tri = np.tril(np.ones((8, 8), np.bool_))

    def launch(tok, q_lens):
        B = len(q_lens)
        caches = ex.init_paged_kv_cache(1 + B, 8)
        probs, out = step(
            tr, ntr, caches, jnp.asarray(1 + np.arange(B, dtype=np.int32)
                                         .reshape(B, 1)),
            jnp.zeros((B,), jnp.int32), jnp.asarray(q_lens, jnp.int32),
            jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (B, 8)),
            jnp.broadcast_to(jnp.asarray(tri), (B, 8, 8)),
            jnp.asarray(tok, jnp.int32))
        return np.asarray(probs), np.asarray(out["__launch_stats__"])

    one = np.zeros((1, 8), np.int32)
    one[0, :5] = IDS[:5]
    three = np.zeros((3, 8), np.int32)      # two idle entries of token 0
    three[0] = one[0]
    p1, s1 = launch(one, [5])
    p3, s3 = launch(three, [5, 0, 0])
    # 5 live rows, 2 experts a token, all 8 experts held
    assert s1[:, 0].tolist() == [10, 10] == s3[:, 0].tolist()
    np.testing.assert_array_equal(s1[:, :3], s3[:, :3])
    np.testing.assert_allclose(p3[0, :5], p1[0, :5], atol=1e-6, rtol=0)
