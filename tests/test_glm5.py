"""GLM-5.3-Flash's block (four residual streams mixed by Sinkhorn-normalised
coefficients around every block, delta-rule linear attention with low-rank
gates, a SPARSE latent layer whose indexer keeps blocks of pooled keys, a
clamped dense SwiGLU, then clamped sigmoid-routed experts with a shared
one) at a tiny size, float32, seeded random weights: the program through
its pages, pooled keys AND states against the plain reference of
benchmark/reference/glm5.py, LOGITS compared.

Sizes (`Glm5Config.tiny` through a configuration's keys): hidden 64, 3
layers dense-KDA / sparse / KDA, 4 heads of 16 (KDA) and of 8 + 8 (latent,
no rope), an indexer of 2 heads of 16 over keys pooled 4 a row that keeps
16 TOKENS a query (3 whole blocks and its own), 8 experts, 2 a token + 1
shared.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import glm5 as fam
from benchmark.reference import glm5 as ref
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.models.glm5 import build_glm5
from flexflow_tpu.ops import hyper_connection as hc
from flexflow_tpu.ops import latent_attention as la
from flexflow_tpu.runtime.executor import node_key

VOCAB = 96
ROWS = 8        # a packed launch's window (PREFILL_WINDOW_ROWS)
TOPK = 16       # tokens a query of the tiny sparse layer keeps


def config(held=(0, 8), **over):
    """A configuration file's keys, at the tiny size: published layers
    0-2 of a period of three. Three Sinkhorn rounds, not 20: at 20 the
    matrix has converged and the control that skips one could not fail."""
    cfg = {
        "family": "glm5", "hidden_size": 64, "num_hidden_layers": 3,
        "first_layer": 0, "first_k_dense_replace": 1,
        "intermediate_size": 96,
        "layer_types": ["linear_attention", "deepseek_sparse_attention",
                        "linear_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse"],
        "linear_attn_config": {
            "num_heads": 4, "head_dim": 16, "gate_lower_bound": -5,
            "short_conv_kernel_size": 4, "kda_layers": [0, 2],
            "full_attn_layers": [1]},
        "kda_gate_rank": 8, "num_attention_heads": 4, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_head_dim": 8,
        "qk_rope_head_dim": 0, "v_head_dim": 8, "mla_use_nope": True,
        "index_n_heads": 2, "index_head_dim": 16, "index_topk": TOPK,
        "index_kpool": 4, "index_kpool_compress": True,
        "index_kpool_always_select_tail": True,
        "indexer_rope_interleave": True, "index_rope_dim": 8,
        "index_rope_theta": 10000.0, "mhc": True, "hc_mult": 4,
        "hc_sinkhorn_iters": 3, "hc_eps": 1e-6, "swiglu_limit": 10,
        "hidden_act": "silu", "attention_bias": False,
        "n_routed_experts": held[1] - held[0], "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "vocab_size": VOCAB,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "torch_dtype": "float32", "experts_held": list(held),
        "published": {"n_routed_experts": 8, "first_k_dense_replace": 1,
                      "num_hidden_layers": 3},
    }
    cfg.update(over)
    return cfg


def build(cfg, seed=5):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_glm5(ff, fam.program_config(cfg), batch_size=1, seq_len=8,
               dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def reference_logp(ff, cfg, ids, **controls):
    w = fam.reference_weights(ff._params[0], cfg)
    logits = functools.partial(ref.logits,
                               arch=fam.reference_arch(cfg, **controls))
    return np.asarray(jax.nn.log_softmax(logits(w, jnp.asarray(ids))),
                      np.float64)


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
        self.dsa_stats = []

    def __call__(self, work, window=ROWS):
        """-> [log-probabilities (rows, V) of each entry of `work`]."""
        items, owner = [], []
        for j, (slot, start, toks) in enumerate(work):
            for off in range(0, len(toks), window):
                items.append((slot, start + off, toks[off:off + window]))
                owner.append(j)
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
        self.dsa_stats.append(
            np.asarray(self.caches.pop("__launch_dsa_stats__")))
        logp = np.log(np.asarray(probs, np.float64))
        return [np.concatenate([logp[i, :len(items[i][2])]
                                for i in range(len(owner)) if owner[i] == j])
                for j in range(len(work))]


def served_logp(ff, ids, cuts, slot=1, mate=None, run=None):
    """`ids` through pages, pooled keys and states: chunks ending at
    `cuts`, then a token a launch; `mate`, another sequence, rides every
    launch in slot 0 in front."""
    run = run or Launches(ff, 3, len(ids))
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
# differently (absorbed against naive attention, the state's read-out, the
# normed row's projection): log-probabilities of magnitude ~5 agree to a
# few float32 ulps of the logits, as in tests/test_ling3.py
TOL = 1e-4


# ---------------------------------------------------------------------------
# the whole block against the reference


@pytest.mark.parametrize("path,cuts", [
    # chunks of 19 and 13 rows: pieces of 8, 8, 3 and 8, 5, so blocks of
    # four tokens are split between items AND between launches, and every
    # row from 16 on selects (44 tokens are 11 blocks, a row keeps 4)
    ("scan", (19, 32)),
    # boundaries that are no multiple of 4, a 1-row and a 2-row chunk
    ("scan", (1, 3, 10, 17, 18, 29, 40)),
    # aligned chunks: every block arrives whole in one item
    ("scan", (16, 32, 40)),
    ("kernel", (19, 32)),
])
def test_prefill_and_decode_through_pages_pooled_keys_and_states(
        tiny, path, cuts, monkeypatch):
    """Chunked prefill to the last cut, then token by token to 44 (past
    `index_topk` = 16 tokens from row 16 on), another sequence beside it
    in every launch, against the reference's one full forward."""
    cfg, ff = tiny
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
        ff = build(cfg)     # its step functions trace under the flag
    got = served_logp(ff, IDS, cuts, mate=MATE)
    want = reference_logp(ff, cfg, IDS)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_slot_resumed_after_preemption_recomputes_its_pooled_keys(tiny):
    """Slot 1 serves 30 rows of one sequence; it is preempted (its pages
    and state left as they are: a stranger's rows, as a page off the free
    list has), then the request is resumed BY RECOMPUTE from row 0 on the
    same pages with other chunk boundaries: the logits are the
    reference's, so every pooled row was zeroed before it was summed."""
    cfg, ff = tiny
    run = Launches(ff, 3, 44)
    served_logp(ff, MATE[:30], (30,), run=run)
    got = served_logp(ff, IDS, (11, 22, 37), run=run)
    np.testing.assert_allclose(got, reference_logp(ff, cfg, IDS), atol=TOL,
                               rtol=0)


def test_the_dense_forward_equals_the_reference(tiny):
    cfg, ff = tiny
    probs = ff.executor.forward_fn()(*ff._params, jnp.asarray(IDS[None]))
    np.testing.assert_allclose(np.log(np.asarray(probs[0], np.float64)),
                               reference_logp(ff, cfg, IDS), atol=TOL,
                               rtol=0)


@pytest.mark.parametrize("control", [
    "drop_tail_block", "pool_before_rope", "skip_sinkhorn_round"])
def test_a_reference_with_another_convention_fails(tiny, control):
    """Each of three conventions decides the result: a reference that
    drops the query's own block (but for the token itself), pools the
    keys before the rope, or stops Sinkhorn a round early is NOT what the
    program computes."""
    cfg, ff = tiny
    got = served_logp(ff, IDS, (19, 32))
    bad = reference_logp(ff, cfg, IDS, **{control: True})
    assert np.abs(got - bad).max() > 100 * TOL
    np.testing.assert_allclose(got, reference_logp(ff, cfg, IDS), atol=TOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# the sparse layer


def _latent(ff):
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.LATENT_ATTENTION)
    return node.attrs, ff._params[0][node_key(node)]


@pytest.mark.parametrize("length,same", [
    (8, True), (TOPK, True), (TOPK + 4, False), (40, False)])
def test_sparse_equals_dense_up_to_index_topk_tokens(tiny, length, same):
    """A context of at most `index_topk` tokens selects everything: the
    layer IS the dense latent layer (the same attrs without an indexer,
    fed the same weights); beyond it the two differ."""
    _cfg, ff = tiny
    attrs, params = _latent(ff)
    dense = dataclasses.replace(attrs, index_heads=0)
    x = jax.random.normal(jax.random.key(2), (1, length, 64), jnp.float32)
    got = np.asarray(la.naive_attention(attrs, x, params))
    want = np.asarray(la.naive_attention(dense, x, params))
    if same:
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    else:
        np.testing.assert_allclose(got[:, :TOPK], want[:, :TOPK], atol=1e-6,
                                   rtol=0)
        assert np.abs(got[:, TOPK:] - want[:, TOPK:]).max() > 1e-3


@pytest.mark.parametrize("k", [1, 3, 7, 40])
def test_select_blocks_is_top_k_with_ties_to_the_lower_index(k):
    """Against `lax.top_k` (stable: a tie to the lower index) on scores
    with many ties, negative values, and rows with fewer valid places
    than k."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-3, 4, (6, 5, 33)).astype(np.float32) / 2
    scores[0, 0] = 0.0                              # one value everywhere
    valid = rng.random((6, 5, 33)) < 0.7
    valid[1, 1] = False                             # nothing to choose
    valid[2, 2, 2:] = False                         # fewer than k
    got = np.asarray(la.select_blocks(jnp.asarray(scores),
                                      jnp.asarray(valid), k))
    masked = jnp.where(jnp.asarray(valid), jnp.asarray(scores), -jnp.inf)
    top, at = jax.lax.top_k(masked, min(k, 33))
    want = np.zeros_like(valid)
    np.put_along_axis(want, np.asarray(at), np.asarray(top) > -np.inf, -1)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def test_a_rope_less_latent_layer_builds_no_rope_part(tiny):
    _cfg, ff = tiny
    attrs, params = _latent(ff)
    assert attrs.qk_rope_head_dim == 0 and attrs.latent_width == 16
    assert params["w_dkv"].shape == (64, 16)
    assert params["w_uq"].shape == (24, 4, 8)
    x = jnp.ones((1, 3, 64), jnp.float32)
    _qn, q_rope, _c, k_r = la.project(attrs, x, params,
                                      jnp.zeros((1, 3), jnp.int32))
    assert q_rope is None and k_r is None


def test_the_pool_holds_pooled_keys_beside_the_latent_rows(tiny):
    """A sparse node's dict: "c" (pages, 8, 128 lanes) and "kp" (pages,
    8 / 4, 16); the pooled keys' bytes a token are counted apart."""
    _cfg, ff = tiny
    specs = ff.executor.paged_kv_cache_specs(9, 8, slots=2)
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.LATENT_ATTENTION)
    entry = specs[node_key(node)]
    assert set(entry) == {"c", "kp"}
    assert entry["c"].shape == (9, 8, 128) and entry["kp"].shape == (9, 2, 16)
    with pytest.raises(ValueError, match="straddle two pages"):
        ff.executor.paged_kv_cache_specs(9, 6, slots=2)


def test_pooled_rows_are_the_blocks_means_and_the_tail_is_partial(tiny):
    """After 19 rows in chunks of 6: blocks 0-3 hold the mean of their
    four keys, block 4 three quarters of a mean in the making (rows
    16-18), whatever the pages held before."""
    _cfg, ff = tiny
    attrs, params = _latent(ff)
    run = Launches(ff, 3, 44)
    key = next(k for k, v in run.caches.items() if "kp" in v)
    run.caches[key]["kp"] = run.caches[key]["kp"] + 7.0    # stale rows
    served_logp(ff, IDS[:19], (6, 12, 18, 19), run=run)
    kp = np.asarray(run.caches[key]["kp"])[run.tables[1]].reshape(-1, 16)
    # the layer's input is not at hand: the invariant is in the sums
    assert np.isfinite(kp).all() and np.abs(kp[:5]).max() < 7.0
    assert np.abs(kp[5:] - 7.0).max() == 0.0      # untouched blocks
    # LayerNorm without bias: a whole key has mean 0 over its 16 values
    # before the rope; the rope keeps pairs' norms, not their sum, so check
    # the un-roped half
    assert np.abs(kp[:4, 8:].mean()) < 0.5


# ---------------------------------------------------------------------------
# the residual streams


def test_hres_is_doubly_stochastic_and_far_from_identity_and_uniform(tiny):
    _cfg, ff = tiny
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.HYPER_CONNECTION
                and n.attrs.part == "pre")
    attrs = dataclasses.replace(node.attrs, sinkhorn_iters=20)
    params = ff._params[0][node_key(node)]
    x = jax.random.normal(jax.random.key(4), (64, 256), jnp.float32)
    pre, post, res = (np.asarray(t) for t in hc.coefficients(attrs, x,
                                                             params))
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-4)
    assert (pre > 0).all() and (pre < 1).all()
    assert (post > 0).all() and (post < 2).all()
    assert np.abs(res - np.eye(4)).mean() > 0.2
    assert np.abs(res - 0.25).mean() > 0.05
    # the token's own part moves the coefficients, not b alone
    assert res.std(axis=0).max() > 0.01


def test_the_four_parts_compose_a_block(tiny):
    """expand -> pre -> post -> sum on a hand-made block output against
    the equations written out with einsum."""
    _cfg, ff = tiny
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.HYPER_CONNECTION
                and n.attrs.part == "pre")
    attrs, params = node.attrs, ff._params[0][node_key(node)]
    e = jax.random.normal(jax.random.key(5), (2, 3, 64), jnp.float32)
    x = hc.expand(attrs, e)
    np.testing.assert_array_equal(np.asarray(x).reshape(2, 3, 4, 64)[:, :, 2],
                                  np.asarray(e))
    h, coef = hc.pre(attrs, x, params)
    pre, post, res = hc.coefficients(attrs, x, params)
    xs = x.reshape(2, 3, 4, 64)
    np.testing.assert_allclose(np.asarray(h), np.asarray(
        jnp.einsum("bsn,bsnc->bsc", pre, xs)), atol=1e-5)
    y = jnp.tanh(h)
    out = hc.post(attrs, x, coef, y)
    want = (jnp.einsum("bsij,bsjc->bsic", res, xs)
            + post[..., None] * y[:, :, None, :])
    np.testing.assert_allclose(np.asarray(out).reshape(2, 3, 4, 64),
                               np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hc.collapse(attrs, out)),
                               np.asarray(want.sum(2)), atol=1e-5)


# ---------------------------------------------------------------------------
# KDA's low-rank gates, the clamp, the shares


def test_low_rank_kda_gates_equal_the_full_rank_op_fed_the_products(tiny):
    from flexflow_tpu.ops import kda_attention as kda

    _cfg, ff = tiny
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.KDA_ATTENTION)
    low, params = node.attrs, ff._params[0][node_key(node)]
    assert low.gate_rank == 8 and "w_f" not in params
    full = dataclasses.replace(low, gate_rank=None)
    wide = {k: v for k, v in params.items()
            if k not in ("w_fa", "w_fb", "w_ga", "w_gb")}
    wide["w_f"] = params["w_fa"] @ params["w_fb"]
    wide["w_g"] = params["w_ga"] @ params["w_gb"]
    assert set(full.weights(node.outputs[0])) == set(wide)
    x = jax.random.normal(jax.random.key(6), (1, 21, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(kda.dense_attention(low, x, params)),
        np.asarray(kda.dense_attention(full, x, wide)), atol=2e-5, rtol=0)


def _moe(ff):
    node = next(n for n in ff.executor.topo
                if n.op_type == OpType.EXPERT_SHARE)
    return node, ff._params[0][node_key(node)]


@pytest.mark.parametrize("path", ["dense_loop", "kernel"])
@pytest.mark.parametrize("limit,live", [(0.0, False), (0.05, True),
                                        (1e6, False)])
def test_the_swiglu_clamp_is_live_at_a_small_limit_and_off_at_zero(
        tiny, path, limit, live, monkeypatch):
    from flexflow_tpu.ops.expert_share import expert_share

    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    cfg, ff = tiny
    node, params = _moe(ff)
    h = jax.random.normal(jax.random.key(7), (13, 64), jnp.float32)
    off, _ = expert_share(dataclasses.replace(node.attrs, swiglu_limit=0.0),
                          h, params)
    got, _ = expert_share(dataclasses.replace(node.attrs,
                                              swiglu_limit=limit), h, params)
    moved = float(jnp.abs(got - off).max())
    assert (moved > 1e-3) if live else (moved == 0.0)
    lyr = fam.reference_weights(ff._params[0], cfg).layers[1].mlp
    arch = fam.reference_arch(cfg)._replace(swiglu_limit=limit)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lyr, arch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def test_the_dense_swiglu_is_clamped_too():
    """A dense layer's gate and up pass through `scalar_min` / `clip`
    nodes; at limit 0 the builder adds none."""
    cfg = config()
    kinds = [n.attrs.kind for n in build(cfg).executor.topo
             if n.op_type == OpType.ELEMENT_UNARY]
    assert kinds.count("scalar_min") == 1 and kinds.count("clip") == 1
    off = [n.attrs.kind for n in build(config(swiglu_limit=0)).executor.topo
           if n.op_type == OpType.ELEMENT_UNARY]
    assert "clip" not in off and "scalar_min" not in off
    small = config(swiglu_limit=0.05)
    ff = build(small)
    got = served_logp(ff, IDS[:20], (20,))
    np.testing.assert_allclose(got, reference_logp(ff, small, IDS[:20]),
                               atol=TOL, rtol=0)
    assert np.abs(got - reference_logp(ff, cfg, IDS[:20])).max() > 100 * TOL


def test_the_eight_chips_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the routed parts of the eight shares
    [e, e + 1) (one of 8 tiny experts a chip, as 36 of 288 are one of
    eight), with the shared expert counted once, are the uncut reference
    layer's expert block; the reference given a share computes that
    share."""
    from flexflow_tpu.ops.expert_share import expert_share

    cfg, ff = tiny
    node, params = _moe(ff)
    assert float(jnp.abs(params["bias"]).max()) > 0     # drawn, not zero
    h = jax.random.normal(jax.random.key(3), (29, 64), jnp.float32)
    lyr = fam.reference_weights(ff._params[0], cfg).layers[1].mlp
    arch = fam.reference_arch(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, lyr, arch)
        shared = ref._swiglu(h, lyr.shared_gate, lyr.shared_up,
                             lyr.shared_down, arch.swiglu_limit)
    total = jnp.zeros_like(h)
    for lo in range(8):
        attrs = dataclasses.replace(node.attrs, held_lo=lo, held_hi=lo + 1)
        part = {k: (v[lo:lo + 1] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in params.items()}
        y, stats = expert_share(attrs, h, part)
        assert int(stats[2]) == 1
        total = total + (y - shared)
        with jax.default_matmul_precision("highest"):
            ref_part = ref._experts(
                h, lyr._replace(w_gate=part["w_gate"], w_up=part["w_up"],
                                w_down=part["w_down"]),
                fam.reference_arch(config(held=(lo, lo + 1))))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_part),
                                   atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# through serve_generation


def test_server_evicts_resumes_and_counts_the_sparse_work(tiny):
    """Two slots and a pool too small for two long requests, a chunk of 6
    (no multiple of 4): the younger request is evicted and resumed by
    recompute, and every request's greedy tokens are the reference's
    argmax. metrics()["sparse"] counts what the rows scored and kept."""
    cfg, ff = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (44, 46, 9, 21)]
    server = ff.serve_generation(
        paged=True, slots=2, max_len=96, page_size=8, num_pages=14,
        prefill_chunk=6, prefix_cache=False)
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
    s = m["sparse"]
    assert s["index_bytes_per_token"] == 16 * 4 // 4    # float32 here
    assert 0 < s["selected_tokens"] < s["context_tokens"]
    assert 0 < s["selected_distinct"] <= s["selected_tokens"]
    assert s["index_blocks_scored"] > 0 and s["index_pages"] > 0
    assert s["hc_rows"] % 6 == 0 and s["hc_rows"] > 0     # six mixings


def test_launch_spans_count_what_the_sparse_form_needs(tiny):
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
    first, second = spans[0], spans[1]
    # rows 0-15: row t scores t // 4 whole blocks and attends to all t + 1
    assert first["index_blocks_scored"] == sum(t // 4 for t in range(16))
    assert first["selected_tokens"] == first["context_tokens"] == 136
    assert first["hc_rows"] == 16 * 6 and first["index_pages"] == 2
    # rows 16-31 keep 3 whole blocks and their own block's t % 4 + 1
    assert second["selected_tokens"] == sum(12 + t % 4 + 1
                                            for t in range(16, 32))
    assert second["context_tokens"] == sum(range(17, 33))
    assert first["index_bytes_per_token"] == 16
    assert first["kv_bytes_per_token"] == 128 * 4      # the latent rows only
    # counted on the device: every token of the first chunk was read once
    assert first["selected_distinct"] == [16]
    assert 16 <= second["selected_distinct"][0] <= 32


@pytest.mark.parametrize("option", [
    {"paged": False}, {"prefix_cache": True}, {"kv_dtype": "int8"},
    {"host_tier": 8}, {"kv_quant_canary": 2}, {"speculate": "spec"},
    {"search_budget": 2}, {"serve_strategy": {}}])
def test_unsupported_serving_options_are_refused_by_name(tiny, option):
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


def test_a_sparse_graph_without_state_layers_is_refused_on_its_own_row():
    """The indexer's row of `_GRAPH_KINDS`, the last, names what IT
    forbids beyond the latent row before it: the prefix cache (a graph
    with state layers is refused by their row first)."""
    from flexflow_tpu.serving import _GRAPH_KINDS

    ff = FFModel(FFConfig(batch_size=1, seed=1, num_devices=1))
    ids = ff.create_tensor((1, 8), DataType.INT32, name="input_ids")
    h = ff.embedding(ids, 32, 64, dtype=DataType.FLOAT, name="emb")
    h = ff.latent_attention(h, 64, 4, 24, 16, 8, 0, 8, 8 ** -0.5,
                            index_heads=2, index_dim=16, index_topk=16,
                            index_rope_dim=8, name="attn")
    ff.softmax(ff.dense(h, 32, use_bias=False, name="head"), name="softmax")
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    rows = [why for is_kind, _r, why in _GRAPH_KINDS
            if is_kind(ff.executor)]
    assert len(rows) == 2 and rows[1].startswith("sparse latent attention")
    with pytest.raises(ValueError, match="prefix_cache.*sparse latent"):
        ff.serve_generation(paged=True, slots=2, max_len=64, page_size=8)
    with pytest.raises(ValueError, match="a sparse latent layer needs"):
        ff.latent_attention(h, 64, 4, None, 16, 8, 0, 8, 1.0, index_heads=2)
