"""Quantized KV pages (flexflow_tpu.paged.quant + the dequant-on-load
paths in paged/attention.py and the scale-aware commit in
runtime/executor.py).

Tolerance contract: an int8 pool is NOT logit-identical to fp32 — the
acceptance criterion is a bounded logit/output delta against the fp32
reference (pinned here at the attention level and, via the
FF_TPU_KV_QUANT_DEBUG shadow cache, at the served-model level), plus
exact TOKEN identity between quantized configurations that must agree
(speculative verify, page sharing, defrag — the page
machinery is a memory layout, never a numerics change *within* a
dtype). Every band asserted here comes from the numerics budget
catalog (flexflow_tpu/analysis/num_budgets.py) by NAME — changing a
tolerance is a reviewed diff of the catalog, and numcheck's budget arm
gates the catalog's own hygiene.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.analysis.num_budgets import tolerance
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.paged.quant import (
    QMAX,
    dequantize_pages,
    quantized_append,
    resolve_kv_dtype,
)
from flexflow_tpu.spec import SpecConfig

# catalog bands (analysis/num_budgets.py) — resolved once by name
ROUNDTRIP = tolerance("int8-kv-roundtrip")          # scale_steps
REGROW = tolerance("int8-kv-commit-regrow")         # scale_steps
MIXED_BATCH = tolerance("int8-kv-mixed-batch")      # abs
SHADOW_DELTA = tolerance("kv-canary-shadow-delta")  # abs
WEIGHT_GRID = tolerance("int8-weight-grid")         # scale_steps
ACCEPT_FLOOR = tolerance("spec-acceptance-floor")   # ratio


def _causal_lm(vocab=512, seed=7):
    lcfg = LlamaConfig(vocab_size=vocab, dim=64, layers=2, heads=4,
                       kv_heads=2, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=seed))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


@pytest.fixture(scope="module")
def lm():
    return _causal_lm()


def _prompts(lcfg, seed=1, lens=(3, 5, 6)):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _serve(ff, prompts, max_new, max_len=32, **kw):
    srv = ff.serve_generation(slots=len(prompts), max_len=max_len,
                              paged=True, page_size=4, **kw)
    try:
        futs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        toks = [np.asarray(f.result(timeout=120)) for f in futs]
        m = srv.metrics()
    finally:
        srv.stop()
    return toks, m


# ---------------------------------------------------------------------------
# quant primitives


def test_quantized_append_grow_only_roundtrip():
    """Appends under grow-only scales: small rows first, then a larger
    row into the SAME page re-quantizes the existing rows at the grown
    scale; everything dequantizes back within half a grid step. Dead
    rows never inflate a scale."""
    N, P, Hkv, D = 4, 4, 1, 3
    pool = jnp.zeros((N, P, Hkv * D), jnp.int8)   # flat-lane pages
    scales = jnp.zeros((N, Hkv), jnp.float32)
    small = jnp.asarray([[[[0.11, -0.07, 0.05]], [[0.02, 0.09, -0.12]]]])
    page = jnp.asarray([[1, 1]])
    off = jnp.asarray([[0, 1]])
    live = jnp.ones((1, 2), bool)
    pool, scales = quantized_append(pool, scales, small, page, off, live)
    s1 = float(scales[1, 0])
    assert s1 == pytest.approx(0.12 / QMAX)
    got = dequantize_pages(pool[1], scales[1])
    np.testing.assert_allclose(np.asarray(got[:2]),
                               np.asarray(small[0, :, 0]),
                               atol=s1 * ROUNDTRIP)

    big = jnp.asarray([[[[1.27, -0.6, 0.3]]]])
    pool, scales = quantized_append(pool, scales, big,
                                    jnp.asarray([[1]]), jnp.asarray([[2]]),
                                    jnp.ones((1, 1), bool))
    s2 = float(scales[1, 0])
    assert s2 == pytest.approx(1.27 / QMAX)   # grew
    got = dequantize_pages(pool[1], scales[1])
    # the ORIGINAL small rows survived the in-place rescale: one
    # round-trip through the old grid plus one through the new one
    np.testing.assert_allclose(np.asarray(got[:2]),
                               np.asarray(small[0, :, 0]),
                               atol=s1 * ROUNDTRIP + s2 * ROUNDTRIP)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(big[0, 0, 0]),
                               atol=s2 * ROUNDTRIP)

    # a dead row full of garbage touches neither payload nor scale
    before = (np.asarray(pool), np.asarray(scales))
    pool, scales = quantized_append(
        pool, scales, jnp.full((1, 1, Hkv, D), 1e6), jnp.asarray([[0]]),
        jnp.asarray([[3]]), jnp.zeros((1, 1), bool))
    np.testing.assert_array_equal(np.asarray(scales), before[1])
    np.testing.assert_array_equal(np.asarray(pool)[1:], before[0][1:])


def test_paged_attention_available_quantized_gate(caplog):
    """int8 pools tile the sublane dim at 32 rows: a page_size that a
    fp32 pool accepts is rejected for int8 WITH a concrete logged
    reason; interpret mode (CI smoke) bypasses the tiling gate."""
    from flexflow_tpu.paged import attention as pa

    pa.reset_rejection_log()
    with caplog.at_level(logging.INFO,
                         logger="flexflow_tpu.paged.attention"):
        assert not pa.paged_attention_available(128, 8, dtype=jnp.int8)
    assert "32-row" in caplog.text and "int8" in caplog.text
    assert pa.paged_attention_available(128, 8, interpret=True,
                                        dtype=jnp.int8)
    assert resolve_kv_dtype("int8") == jnp.int8
    assert resolve_kv_dtype("auto") is None
    with pytest.raises(ValueError, match="kv_dtype"):
        resolve_kv_dtype("int7")


# ---------------------------------------------------------------------------
# attention-level tolerance: the mixed ragged batch, both paths


def _mixed_ragged_outputs(quantized: bool):
    """Two ragged_paged_attention calls against one pool: a 4-row chunk
    per slot (prefix fill), then a mixed batch — slot 0 decode, slot 1
    chunk, slot 2 a 3-node tree. Returns the live output rows of the
    second call."""
    from flexflow_tpu.paged.attention import (chain_descriptor,
                                              ragged_paged_attention)

    B, S, H, Hkv, D = 3, 4, 2, 1, 8
    N, P = 10, 4
    rs = np.random.RandomState(3)
    pt = jnp.asarray([[1 + 3 * b + j for j in range(3)]
                      for b in range(B)], jnp.int32)
    scale = 1.0 / np.sqrt(D)

    def rnd(*shape):
        return jnp.asarray(rs.randn(*shape).astype(np.float32))

    if quantized:
        kc = jnp.zeros((N, P, Hkv * D), jnp.int8)
        vc = jnp.zeros((N, P, Hkv * D), jnp.int8)
        ks = jnp.zeros((N, Hkv), jnp.float32)
        vs = jnp.zeros((N, Hkv), jnp.float32)
        sc = {"k_scales": ks, "v_scales": vs}
    else:
        kc = jnp.zeros((N, P, Hkv * D), jnp.float32)
        vc = jnp.zeros((N, P, Hkv * D), jnp.float32)
        sc = {}

    # phase 1: causal 4-token chunk at pos 0 for every slot
    q1, k1, v1 = rnd(B, S, H, D), rnd(B, S, Hkv, D), rnd(B, S, Hkv, D)
    qlen, depths, anc = chain_descriptor(B, S)
    out = ragged_paged_attention(q1, k1, v1, kc, vc, pt,
                                 jnp.zeros((B,), jnp.int32), qlen, depths,
                                 anc, scale=scale, rope_theta=10000.0,
                                 **sc)
    if quantized:
        _, kc, vc, ks, vs = out
        sc = {"k_scales": ks, "v_scales": vs}
    else:
        _, kc, vc = out

    # phase 2: decode (1 row) + chunk (4 rows) + tree (3 nodes)
    pos = jnp.asarray([4, 4, 4], jnp.int32)
    q_lens = jnp.asarray([1, 4, 3], jnp.int32)
    depths = jnp.asarray([[0, 0, 0, 0], [0, 1, 2, 3], [0, 1, 1, 0]],
                         jnp.int32)
    anc = np.zeros((B, S, S), bool)
    anc[0, 0, 0] = True
    anc[1] = np.tril(np.ones((S, S), bool))
    anc[2, 0, 0] = True
    anc[2, 1, [0, 1]] = True
    anc[2, 2, [0, 2]] = True
    q2, k2, v2 = rnd(B, S, H, D), rnd(B, S, Hkv, D), rnd(B, S, Hkv, D)
    out2 = ragged_paged_attention(q2, k2, v2, kc, vc, pt, pos, q_lens,
                                  jnp.asarray(depths), jnp.asarray(anc),
                                  scale=scale, rope_theta=10000.0, **sc)[0]
    o = np.asarray(out2)
    return np.concatenate([o[b, :int(q_lens[b])].ravel()
                           for b in range(B)])


def _blocked_ragged_outputs(quantized: bool):
    """The mixed batch again, over a pool filled beforehand and a table
    WIDER than the kernel's block (page 32, 8 kv heads x 128: an int8
    pool walks blocks of 32 pages, the table has 40): a decode row whose
    horizon ends inside the second block, a chunk and a tree whose
    windows straddle the first boundary, a padded entry between live
    ones, a chunk on the table's last rows. Returns the live rows."""
    from flexflow_tpu.paged.attention import (ragged_block_pages,
                                              ragged_paged_attention)

    B, S, H, Hkv, D = 5, 4, 8, 8, 128
    P, MAXP = 32, 40
    N = B * MAXP + 1
    K = P * ragged_block_pages(P, MAXP, Hkv * D, jnp.int8, (H // Hkv) * S)
    assert K < MAXP * P
    rs = np.random.RandomState(7)
    rows = jnp.asarray(rs.randn(2, N, P, Hkv, D).astype(np.float32))
    if quantized:
        page = jnp.broadcast_to(jnp.arange(N)[:, None], (N, P))
        off = jnp.broadcast_to(jnp.arange(P)[None], (N, P))
        (kc, ks), (vc, vs) = (
            quantized_append(jnp.zeros((N, P, Hkv * D), jnp.int8),
                             jnp.zeros((N, Hkv), jnp.float32), x, page,
                             off, jnp.ones((N, P), bool)) for x in rows)
        sc = {"k_scales": ks, "v_scales": vs}
    else:
        kc, vc = (x.reshape(N, P, Hkv * D) for x in rows)
        sc = {}
    pt = jnp.asarray((rs.permutation(N - 1) + 1)
                     .reshape(B, MAXP).astype(np.int32))
    pos = jnp.asarray([K + 37, K - 2, 0, K - 1, MAXP * P - S], jnp.int32)
    q_lens = jnp.asarray([1, 4, 0, 3, 4], jnp.int32)
    depths = jnp.asarray([[0, 0, 0, 0], [0, 1, 2, 3], [0, 0, 0, 0],
                          [0, 1, 1, 0], [0, 1, 2, 3]], jnp.int32)
    anc = np.zeros((B, S, S), bool)
    anc[0, 0, 0] = True
    anc[1] = anc[4] = np.tril(np.ones((S, S), bool))
    anc[3, 0, 0] = True
    anc[3, 1, [0, 1]] = True
    anc[3, 2, [0, 2]] = True
    q, k, v = (jnp.asarray(rs.randn(B, S, h, D).astype(np.float32))
               for h in (H, Hkv, Hkv))
    out = ragged_paged_attention(q, k, v, kc, vc, pt, pos, q_lens, depths,
                                 jnp.asarray(anc), scale=1.0 / np.sqrt(D),
                                 rope_theta=10000.0, **sc)[0]
    o = np.asarray(out)
    return np.concatenate([o[b, :int(q_lens[b])].ravel()
                           for b in range(B)])


@pytest.mark.parametrize("batch", [_mixed_ragged_outputs,
                                   _blocked_ragged_outputs],
                         ids=["one-block", "blocks"])
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["gather", "interpret-kernel"])
def test_mixed_ragged_batch_quantized_tolerance(interpret, batch,
                                                monkeypatch):
    """int8 pool vs fp32 pool on the same mixed decode/chunk/tree batch:
    live output rows agree within a small tolerance on BOTH attention
    paths (the Pallas kernel's dequant-on-load and the gather
    fallback's), and quantization really happened (delta > 0)."""
    if interpret:
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("FF_TPU_FLASH_INTERPRET", raising=False)
    ref = batch(quantized=False)
    got = batch(quantized=True)
    err = float(np.max(np.abs(got - ref)))
    assert 0.0 < err < MIXED_BATCH, err


def test_scale_aware_commit_copies_across_scales(lm):
    """The spec-commit row copy on a quantized pool: copying rows from a
    LARGE-scale source page grows the destination's scale (re-snapping
    its existing rows), while a SMALL-scale source leaves the
    destination's payload bytes outside the copied rows untouched."""
    ff, _ = lm
    commit = ff.executor.paged_commit_fn()
    P, Hkv, D = 4, 1, 2
    rs = np.random.RandomState(5)
    small = rs.uniform(-0.1, 0.1, (P, Hkv, D)).astype(np.float32)
    big = rs.uniform(-2.0, 2.0, (P, Hkv, D)).astype(np.float32)

    def build():
        pool = jnp.zeros((3, P, Hkv * D), jnp.int8)
        scales = jnp.zeros((3, Hkv), jnp.float32)
        for pg, rows in ((1, small), (2, big)):
            pool, scales = quantized_append(
                pool, scales, jnp.asarray(rows)[None],
                jnp.full((1, P), pg), jnp.arange(P)[None],
                jnp.ones((1, P), bool))
        # the commit consumes the pool it is given: a buffer each for
        # key and value, never one handed in twice
        return {"n": {"k": pool, "v": jnp.array(pool), "k_scale": scales,
                      "v_scale": jnp.array(scales)}}

    pt = jnp.asarray([[1, 2]], jnp.int32)   # cache rows 0..3 -> page 1

    # big -> small: rows 4,5 (page 2) onto rows 0,1 (page 1); row 2
    # self-copies (the unused-entry encoding)
    out = commit(build(), pt, jnp.asarray([[4, 5, 2]]),
                 jnp.asarray([[0, 1, 2]]))["n"]
    s_dst = float(out["k_scale"][1, 0])
    assert s_dst == pytest.approx(float(np.abs(big).max()) / QMAX)
    got = np.asarray(dequantize_pages(out["k"][1], out["k_scale"][1]))
    np.testing.assert_allclose(got[:2], big[:2, 0], atol=s_dst * REGROW)
    # surviving rows re-snapped to the grown grid, still within it
    np.testing.assert_allclose(got[2:], small[2:, 0], atol=s_dst * REGROW)

    # small -> big: the destination's scale and untouched bytes are
    # byte-identical (no grow, ratio 1)
    ref = build()["n"]
    out = commit(build(), pt, jnp.asarray([[0, 1, 6]]),
                 jnp.asarray([[4, 5, 6]]))["n"]
    np.testing.assert_array_equal(np.asarray(out["k_scale"][2]),
                                  np.asarray(ref["k_scale"][2]))
    np.testing.assert_array_equal(np.asarray(out["k"][2, 2:]),
                                  np.asarray(ref["k"][2, 2:]))
    got = np.asarray(dequantize_pages(out["k"][2], out["k_scale"][2]))
    s_big = float(ref["k_scale"][2, 0])
    np.testing.assert_allclose(got[:2], np.asarray(
        dequantize_pages(ref["k"][1], ref["k_scale"][1]))[:2],
        atol=s_big * ROUNDTRIP)


# ---------------------------------------------------------------------------
# served-model tolerance and stability


def test_greedy_int8_server_within_tolerance(lm, monkeypatch):
    """Greedy decode from an int8 pool vs the dense fp32 reference: the
    FF_TPU_KV_QUANT_DEBUG shadow cache pins the output-probability delta
    under 1e-2 (measured ~1e-4); token streams may legitimately flip on
    near-flat logits, so a MAJORITY must match, not all."""
    monkeypatch.setenv("FF_TPU_KV_QUANT_DEBUG", "1")
    ff, lcfg = lm
    prompts = _prompts(lcfg)
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    got, m = _serve(ff, prompts, 6, kv_dtype="int8")
    assert m["kv_cache_dtype"] == "int8"
    assert 0.0 < m["kv_quant_error"] < SHADOW_DELTA, m["kv_quant_error"]
    matched = sum(np.array_equal(w, g) for w, g in zip(want, got))
    assert matched >= len(prompts) - 1, (matched, want, got)


def test_spec_acceptance_floor_on_quantized_pool():
    """Speculative decode over an int8 pool on the token-cyclic fixture:
    acceptance stays above the same floor as fp (the drafter predicts
    the cycle; quantized verify must not reject it), and the emitted
    stream is token-identical to the plain int8 paged path."""
    from flexflow_tpu.spec.fixtures import make_token_cyclic

    ff, lcfg = _causal_lm(vocab=64)
    make_token_cyclic(ff)
    rs = np.random.RandomState(11)
    prompt = rs.randint(0, lcfg.vocab_size, (6,)).astype(np.int32)

    plain, _ = _serve(ff, [prompt], 40, max_len=64, kv_dtype="int8")
    srv = ff.serve_generation(slots=2, max_len=64, paged=True, page_size=4,
                              speculate=SpecConfig(width=2, depth=4),
                              kv_dtype="int8")
    try:
        got = np.asarray(srv.submit(prompt, max_new_tokens=40)
                         .result(timeout=120))
        m = srv.metrics()
    finally:
        srv.stop()
    np.testing.assert_array_equal(plain[0], got)
    spec = m["speculative"]
    assert spec["accepted_tokens_per_step"] >= ACCEPT_FLOOR, spec
    assert 0.0 < spec["acceptance_rate"] <= 1.0
    assert m["kv_cache_dtype"] == "int8"


def test_cow_divergence_with_quantized_shared_pages(lm):
    """Two requests share a quantized prefix's pages then diverge: each
    stream is token-identical to its solo int8 run — COW isolation keeps
    one request's appends (and scale grows) out of the other's pages.
    prefill_chunk == page_size so cached pages quantize identically."""
    ff, lcfg = lm
    rs = np.random.RandomState(15)
    sys_prompt = rs.randint(0, lcfg.vocab_size, (8,)).astype(np.int32)
    a, b = [np.concatenate([sys_prompt,
                            rs.randint(0, lcfg.vocab_size, (2,))
                            .astype(np.int32)]) for _ in range(2)]
    solo_a, _ = _serve(ff, [a], 8, kv_dtype="int8", prefill_chunk=4)
    solo_b, _ = _serve(ff, [b], 8, kv_dtype="int8", prefill_chunk=4)

    srv = ff.serve_generation(slots=3, max_len=32, paged=True, page_size=4,
                              prefill_chunk=4, kv_dtype="int8")
    try:
        warm = srv.submit(sys_prompt, max_new_tokens=1)
        warm.result(timeout=120)
        futs = [srv.submit(p, max_new_tokens=8) for p in (a, b)]
        got = [np.asarray(f.result(timeout=120)) for f in futs]
        m = srv.metrics()
    finally:
        srv.stop()
    np.testing.assert_array_equal(solo_a[0], got[0])
    np.testing.assert_array_equal(solo_b[0], got[1])
    assert m["prefix_cache"]["hit_tokens"] >= 2 * 8
    assert m["kv_cache_dtype"] == "int8"


def test_defrag_with_shared_quantized_pages(lm):
    """Defrag while live requests share quantized prefix pages: the
    permutation moves int8 payload AND scale sidecar together, so the
    streams are identical to the no-defrag int8 run."""
    ff, lcfg = lm
    rs = np.random.RandomState(15)
    sys_prompt = rs.randint(0, lcfg.vocab_size, (8,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rs.randint(0, lcfg.vocab_size, (2,))
                               .astype(np.int32)]) for _ in range(3)]

    def run(defrag):
        srv = ff.serve_generation(slots=3, max_len=32, paged=True,
                                  page_size=4, prefill_chunk=4,
                                  kv_dtype="int8")
        try:
            first = srv.submit(prompts[0], max_new_tokens=8)
            first.result(timeout=120)
            futs = [srv.submit(p, max_new_tokens=8) for p in prompts[1:]]
            if defrag:
                srv.request_defrag()
            got = [np.asarray(first.result())] + \
                  [np.asarray(f.result(timeout=120)) for f in futs]
            return got, srv.defrags
        finally:
            srv.stop()

    want, _ = run(defrag=False)
    got, defrags = run(defrag=True)
    assert defrags >= 1
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


# ---------------------------------------------------------------------------
# kv_quant_error canary: sampled production shadow windows (ISSUE 15)


def test_kv_quant_canary_samples_windows(lm, monkeypatch):
    """kv_quant_canary=1 opens a shadow window on every admission: the
    kv_quant_error gauge populates in PRODUCTION (no debug env) at
    sampled cost, the window counter ticks, windows close on release,
    and the canary is observe-only — tokens identical to the
    canary-less int8 run."""
    monkeypatch.delenv("FF_TPU_KV_QUANT_DEBUG", raising=False)
    ff, lcfg = lm
    prompts = _prompts(lcfg)
    plain, _ = _serve(ff, prompts, 6, kv_dtype="int8")
    got, m = _serve(ff, prompts, 6, kv_dtype="int8", kv_quant_canary=1)
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)
    can = m["kv_quant_canary"]
    assert can["every"] == 1 and can["debug_mode"] is False
    assert can["windows"] >= 1
    assert can["window_open"] is False           # all requests released
    assert 0.0 < m["kv_quant_error"] < SHADOW_DELTA, m["kv_quant_error"]
    # the breach threshold comes from the num_budgets catalog, and a
    # healthy run stays under it
    assert can["threshold"] == SHADOW_DELTA
    assert can["breaches"] == 0
    # the dtype plan the Executor exported matches the live pool: int8
    # pages lower as s8, and the /v2 model block reports the match
    model = m["model"]
    assert model["dtype_plan"]["paged_decode"]["kv"] == "s8"
    assert model["dtype_plan"]["paged_decode"]["accum"] == "f32"
    assert model["dtype_plan_ok"] is True

    with pytest.raises(ValueError, match="kv_quant_canary"):
        ff.serve_generation(slots=1, max_len=16, paged=True, page_size=4,
                            kv_dtype="int8", kv_quant_canary=-1)
    # the dense path has no pool to probe
    with pytest.raises(ValueError, match="paged"):
        ff.serve_generation(slots=1, max_len=16, kv_quant_canary=1)


def test_kv_quant_canary_env_and_debug_precedence(lm, monkeypatch):
    """FF_TPU_KV_QUANT_CANARY configures the rate without code changes;
    FF_TPU_KV_QUANT_DEBUG=1 (the all-requests shadow) takes precedence
    and disables sampling."""
    ff, lcfg = lm
    monkeypatch.setenv("FF_TPU_KV_QUANT_CANARY", "2")
    srv = ff.serve_generation(slots=1, max_len=16, paged=True, page_size=4,
                              kv_dtype="int8")
    try:
        assert srv.metrics()["kv_quant_canary"]["every"] == 2
    finally:
        srv.stop()
    monkeypatch.setenv("FF_TPU_KV_QUANT_DEBUG", "1")
    srv = ff.serve_generation(slots=1, max_len=16, paged=True, page_size=4,
                              kv_dtype="int8", kv_quant_canary=3)
    try:
        can = srv.metrics()["kv_quant_canary"]
        assert can["every"] == 0 and can["debug_mode"] is True
        assert can["window_open"] is True        # the debug shadow is on
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# weight storage casts (init_params(weight_dtype=...))


def test_init_params_weight_dtype_casts(lm):
    ff, _ = lm
    rng = jax.random.key(0)
    for name, want in (("bf16", jnp.bfloat16),
                       ("fp8", jnp.float8_e4m3fn)):
        tr, ntr = ff.executor.init_params(rng, weight_dtype=name)
        for leaf in jax.tree_util.tree_leaves((tr, ntr)):
            assert leaf.dtype == want, (name, leaf.dtype)


def test_init_params_int8_fake_quant_snaps_to_grid(lm):
    """int8 weight storage is modeled as fake quantization: every leaf
    is stored bf16 but holds at most 255 distinct values (the symmetric
    per-leaf grid), and stays within half a grid step of the fp draw."""
    ff, _ = lm
    rng = jax.random.key(0)
    tr, _ = ff.executor.init_params(rng, weight_dtype="int8")
    ref, _ = ff.executor.init_params(rng)
    checked = 0
    for nk, ws in tr.items():
        for wn, leaf in ws.items():
            assert leaf.dtype == jnp.bfloat16
            vals = np.unique(np.asarray(leaf, np.float32))
            assert len(vals) <= 255
            full = np.asarray(ref[nk][wn], np.float32)
            step = np.abs(full).max() / QMAX
            # grid snap (<= step/2) plus the bf16 storage round-off
            tol = step * WEIGHT_GRID + np.abs(full).max() / 128.0
            assert np.abs(np.asarray(leaf, np.float32) - full).max() \
                <= tol
            checked += 1
    assert checked > 0
    with pytest.raises(ValueError, match="weight_dtype"):
        ff.executor.init_params(rng, weight_dtype="int4")
