"""shapecheck runtime soundness gate (ISSUE 14): after catalog-driven
warmup, mixed packed and speculative serving must observe
compile events that are (a) all pre-steady-state — `steady_state_recompiles`
pinned at ZERO — and (b) a subset of the statically enumerated catalog
(`check_soundness` empty). Plus the satellite contracts that ride the
same machinery: the TTFT compile/serve split on per-request records.

CI runs the same gate as a smoke step (.github/workflows/tests.yml);
tests/test_analysis.py holds the static-arm seeded defects.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.analysis.shapecheck import check_soundness
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.spec import SpecConfig


def _causal_lm(seed=7):
    lcfg = LlamaConfig(vocab_size=512, dim=64, layers=2, heads=4,
                       kv_heads=2, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=seed))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


@pytest.fixture(scope="module")
def gate_model():
    return _causal_lm()


def _serve_mixed(server, rs, vocab):
    """Mixed traffic: greedy + sampled, short + chunk-spanning prompts —
    every steady-state launch family the server can dispatch."""
    prompts = [rs.randint(0, vocab, (n,)).astype(np.int32)
               for n in (3, 9, 5)]
    futs = [server.submit(p, max_new_tokens=6, temperature=t)
            for p, t in zip(prompts, (0.0, 0.5, 0.0))]
    outs = [f.result(timeout=600) for f in futs]
    assert all(len(o) >= 1 for o in outs)


def test_warmed_serving_observes_only_catalog_shapes_and_never_recompiles(
        gate_model):
    """THE soundness gate: warm from the static catalog, serve mixed
    traffic, then require zero steady-state recompiles and every
    observed compile event enumerated. Runs the default server and
    a speculative server back to back on ONE model — which also proves
    the per-server event scoping on the shared executor tracker (the
    spec server's warm compiles must not read as the first server's
    steady-state recompiles, and vice versa)."""
    ff, lcfg = gate_model
    rs = np.random.RandomState(0)

    flavors = (
        dict(),
        dict(speculate=SpecConfig(width=2, depth=2)),
    )
    for kwargs in flavors:
        server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                     page_size=4, prefill_chunk=6,
                                     **kwargs)
        try:
            catalog = server.warm_launch_shapes()
            warm_events = server.compile_events()
            # warm did real work, and every warm compile is enumerated
            assert warm_events, kwargs
            assert check_soundness(catalog, warm_events) == []

            _serve_mixed(server, rs, lcfg.vocab_size)

            comp = server.metrics()["compile"]
            assert comp["steady_state_recompiles"] == 0, (kwargs, comp)
            events = server.compile_events()
            steady = [ev for ev in events if ev["steady_state"]]
            assert steady == [], (kwargs, steady)
            unsound = check_soundness(catalog, events)
            assert unsound == [], \
                (kwargs, [f.message for f in unsound])
            assert comp["jit_cache_entries"] >= 1
        finally:
            server.stop()


def test_shrunk_catalog_fails_soundness_against_live_events():
    """Seeded defect (runtime half): delete one enumerated shape from
    the catalog a live server actually compiled under — check_soundness
    must produce shape-catalog-unsound naming the witness event. Proves
    the gate can actually fail, not just pass vacuously. Needs a fresh
    model: a shared executor's jit caches would already hold every
    shape, and an event-free warm can't witness anything."""
    ff, lcfg = _causal_lm(seed=5)
    server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                 page_size=4, prefill_chunk=6)
    try:
        catalog = server.warm_launch_shapes()
        events = server.compile_events()
    finally:
        server.stop()
    decode = [ev for ev in events
              if ev["entry"] == "ragged_step" and ev["shape"] == (2, 1)]
    assert decode, events  # the decode tick always compiles
    catalog["entries"]["ragged_step"]["shapes"].remove([2, 1])
    findings = check_soundness(catalog, events)
    assert findings and all(f.code == "shape-catalog-unsound"
                            for f in findings)
    assert any("ragged_step" in f.where for f in findings)


def test_ttft_records_split_compile_from_serve_time():
    """Per-request records carry first_compile_s / ttft_excl_compile_s:
    a COLD first request's TTFT is
    dominated by compiles; after warm_launch_shapes the same prompt pays
    none. Fresh model so the cold half sees real compiles."""
    ff, lcfg = _causal_lm(seed=11)
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, lcfg.vocab_size, (5,)).astype(np.int32)

    server = ff.serve_generation(slots=2, max_len=32, paged=False)
    try:
        server.generate(prompt, max_new_tokens=4)
        cold, = server.metrics()["requests"]
        assert cold["first_compile_s"] > 0.1, cold
        assert cold["ttft_excl_compile_s"] < cold["ttft_s"], cold
        assert cold["ttft_s"] - cold["ttft_excl_compile_s"] == \
            pytest.approx(cold["first_compile_s"], abs=1e-6)

        # steady request: shapes already compiled, the split collapses
        server.generate(prompt, max_new_tokens=4)
        warm = server.metrics()["requests"][-1]
        assert warm["first_compile_s"] == 0.0, warm
        assert warm["ttft_excl_compile_s"] == pytest.approx(
            warm["ttft_s"]), warm
    finally:
        server.stop()

    # a warmed server's FIRST request already pays nothing
    server = ff.serve_generation(slots=2, max_len=32, paged=False)
    try:
        server.warm_launch_shapes()
        server.generate(prompt, max_new_tokens=4)
        first, = server.metrics()["requests"]
        assert first["first_compile_s"] == 0.0, first
    finally:
        server.stop()
