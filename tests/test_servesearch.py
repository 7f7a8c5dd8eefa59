"""Serving-strategy search (search/servesearch.py + search/traffic.py +
the tick pricing in search/cost_model.py).

Contracts under test: the tick pricer is monotone in the things that
cost real time (launch rows, padding, spec tree size, prefill chunk) and
pays the host once a dispatch; the search REUSES
the existing anneal/DP drivers, is deterministic under a fixed seed, and
strictly beats the hand default on the named traffic profiles; fftrace
calibration reports are consumed when fresh (changing the priced
metrics) and refused when stale or unstamped; and a searched strategy is
SERVABLE — serve_generation(serve_strategy=...) emits tokens identical
to dense generate.
"""

import json
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.search import traffic as traffic_mod
from flexflow_tpu.search.cost_model import (
    HOST_DISPATCH_SECONDS,
    CostModel,
    TickPricer,
    kv_cache_token_bytes,
)
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.servesearch import (
    PricedLayout,
    ServeObjective,
    ServePricer,
    ServeSearchResult,
    ServeStrategy,
    default_space,
    load_calibration,
    search_serve_strategy,
)
from flexflow_tpu.spec import SpecConfig


# ---------------------------------------------------------------------------
# tick pricing


def _pricer(**kw):
    return TickPricer(base_step_s=1e-3, base_tokens=256, **kw)


def test_decode_dispatch_monotone_in_live_rows():
    p = _pricer()
    costs = [p.decode_dispatch(r) for r in (1, 2, 4, 8, 16)]
    assert costs == sorted(costs)
    assert costs[0] < costs[-1]


def test_decode_dispatch_padding_costs_less_than_live():
    p = _pricer()
    base = p.decode_dispatch(4)
    padded = p.decode_dispatch(4, padded_rows=4)
    live = p.decode_dispatch(8)
    assert base < padded < live  # padded rows cost, but under full price


def test_verify_dispatch_monotone_in_tree_nodes():
    p = _pricer()
    costs = [p.verify_dispatch(4, nodes) for nodes in (1, 3, 9, 15)]
    assert costs == sorted(costs)
    assert costs[0] < costs[-1]


def test_prefill_tick_monotone_in_chunk():
    p = _pricer()
    costs = [p.prefill_tick(c) for c in (16, 32, 64, 128)]
    assert costs == sorted(costs)
    assert costs[0] < costs[-1]


def test_tick_scale_multiplies_compute_only():
    plain = _pricer()
    seen = []

    def scale(phase, batch, chunk, width):
        seen.append((phase, batch, chunk, width))
        return 2.0

    scaled = _pricer(tick_scale=scale)
    for kind in ("decode", "verify", "prefill"):
        if kind == "decode":
            a, b = plain.decode_dispatch(4), scaled.decode_dispatch(4)
        elif kind == "verify":
            a, b = plain.verify_dispatch(4, 7), scaled.verify_dispatch(4, 7)
        else:
            a, b = plain.prefill_tick(32), scaled.prefill_tick(32)
        assert b - HOST_DISPATCH_SECONDS == pytest.approx(
            2.0 * (a - HOST_DISPATCH_SECONDS))
    assert {s[0] for s in seen} == {"decode", "verify", "prefill"}


def test_expected_tokens_per_step_bounds():
    spec = SpecConfig(width=2, depth=4)
    assert spec.expected_tokens_per_step(0.0) == pytest.approx(1.0)
    assert spec.expected_tokens_per_step(1.0) == pytest.approx(5.0)
    mid = spec.expected_tokens_per_step(0.6)
    assert 1.0 < mid < 5.0
    # monotone in acceptance
    vals = [spec.expected_tokens_per_step(a)
            for a in (0.1, 0.3, 0.5, 0.7, 0.9)]
    assert vals == sorted(vals)


# ---------------------------------------------------------------------------
# graph-level pieces (no compile: shape-inferred graph + cost model)


def _graph():
    ff = FFModel(FFConfig(batch_size=4, num_devices=1))
    build_llama(ff, LlamaConfig.tiny(vocab=512), batch_size=4, seq_len=64,
                dtype=DataType.FLOAT)
    ff.graph.infer_shapes()
    return ff.graph


@pytest.fixture(scope="module")
def graph():
    return _graph()


def _cost(axes=None):
    return CostModel(TPUMachineModel.make("v5e", 8),
                     axes or {"data": 2, "model": 4})


def test_kv_cache_token_bytes_positive(graph):
    b = kv_cache_token_bytes(graph)
    assert isinstance(b, int) and b > 0
    # K and V, float32, at least one layer's worth of kv heads
    assert b % 2 == 0


# ---------------------------------------------------------------------------
# ServeStrategy surface


def test_strategy_validate_rejects_page_over_max_len():
    with pytest.raises(ValueError):
        ServeStrategy(page_size=128).validate(max_len=64)


def test_strategy_json_roundtrip():
    s = ServeStrategy(page_size=16, prefill_chunk=32, spec_width=2,
                      spec_depth=3, pool_fraction=0.5,
                      mesh=(("data", 2), ("model", 4)))
    assert ServeStrategy.from_json(s.to_json()) == s
    assert ServeStrategy.from_json(json.loads(json.dumps(s.to_json()))) == s


@pytest.mark.parametrize("stored", [True, False])
def test_strategy_from_json_stored_ragged_pack(stored):
    """A strategy JSON stored before the packing stopped being an option
    is outside input: `"ragged_pack": true` (packed, what there is) is
    dropped; `false` names a launch that no longer exists and is refused
    by the key's name, not as a dataclass TypeError."""
    s = ServeStrategy(page_size=16, prefill_chunk=32)
    doc = dict(s.to_json(), ragged_pack=stored)
    if stored:
        assert ServeStrategy.from_json(doc) == s
    else:
        with pytest.raises(ValueError, match="ragged_pack"):
            ServeStrategy.from_json(doc)
    assert "ragged_pack" not in s.to_json()


@pytest.mark.parametrize("how", ["default", "set"])
@pytest.mark.parametrize("key,default,set_to", [
    ("megastep_ticks", 1, 8), ("megastep_mixed", False, True),
    ("overlap_dispatch", False, True)],
    ids=["megastep_ticks", "megastep_mixed", "overlap_dispatch"])
def test_strategy_from_json_stored_megastep_keys(key, default, set_to, how):
    """A strategy JSON stored while a server had device-resident loops
    names them: at its default a key is dropped (that strategy ran the
    loop there is), set it asked for a loop that no longer exists and is
    refused by the key's name, not as a dataclass TypeError."""
    s = ServeStrategy(page_size=16, prefill_chunk=32)
    doc = dict(s.to_json(), **{key: default if how == "default" else set_to})
    if how == "default":
        assert ServeStrategy.from_json(doc) == s
        assert ServeStrategy.from_json(doc).fingerprint() == s.fingerprint()
    else:
        with pytest.raises(ValueError, match=key):
            ServeStrategy.from_json(doc)
    assert key not in s.to_json()


def test_strategy_kv_dtype_knob_surface():
    """The kv_dtype knob: validated at strategy level (a typo fails the
    search proposal, never a silently-fp32 served pool), threaded into
    the server kwargs, shown in describe(), searchable, and absent from
    OLD persisted strategies (which load as "auto")."""
    s = ServeStrategy(page_size=32, kv_dtype="int8")
    s.validate(max_len=128)
    assert s.to_server_kwargs(slots=4, max_len=128)["kv_dtype"] == "int8"
    assert "kv int8" in s.describe()
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeStrategy(kv_dtype="int7").validate(max_len=128)
    assert ServeStrategy.from_json(s.to_json()) == s
    old = s.to_json()
    old.pop("kv_dtype")
    assert ServeStrategy.from_json(old).kv_dtype == "auto"
    assert "kv_dtype" in default_space(max_len=128)


def test_pricer_rebills_pool_per_kv_dtype():
    """ServePricer re-prices the pool's HBM bill from the layout's
    dtype-independent element counts: int8 bills 1 byte/elem plus the
    per-page scale sidecar, bf16 bills 2 bytes/elem, auto keeps the
    model-dtype bytes — all without re-walking the graph."""
    lay = PricedLayout(axis_sizes={}, strategy={}, step_s=1e-3,
                       base_tokens=256, mem_bytes=1e6, kv_token_bytes=512,
                       mode="test", kv_token_elems=128, kv_scale_elems=16)
    stats = traffic_mod.get_profile("smoke").prompt_stats()
    pr = ServePricer([lay], stats, slots=4, max_len=128)
    auto = pr.metrics(ServeStrategy(page_size=32))
    q = pr.metrics(ServeStrategy(page_size=32, kv_dtype="int8"))
    bf = pr.metrics(ServeStrategy(page_size=32, kv_dtype="bf16"))
    assert auto["kv_token_bytes"] == 512.0
    # 128 int8 payload bytes + ceil(16 scales * 4 B / 32-token page)
    assert q["kv_token_bytes"] == 128.0 + 2.0
    assert bf["kv_token_bytes"] == 256.0
    assert q["hbm_bytes"] < bf["hbm_bytes"] < auto["hbm_bytes"]


# ---------------------------------------------------------------------------
# traffic profiles


def test_profiles_registry():
    assert set(traffic_mod.PROFILES) == {
        "smoke", "shared-system-prompt", "mixed-length",
        "long-context-summarization", "agentic-multiturn"}
    with pytest.raises(KeyError):
        traffic_mod.get_profile("nope")


def test_production_profile_shapes():
    """The two production-shaped profiles (ISSUE 15 satellite): long-
    context summarization is prefill-heavy with no shared prefix;
    agentic multi-turn opens every request with a deep (4-page) shared
    prefix and short per-turn suffixes."""
    lc = traffic_mod.get_profile("long-context-summarization", page_size=8,
                                 requests=5)
    s = lc.sample(np.random.RandomState(0), vocab=128)
    assert s.shared_prefix is None
    for p in s.prompts:
        assert 24 <= len(p) <= 40          # 3..5 pages of prompt
    st = lc.prompt_stats()
    assert st["prefix_share_rate"] == 0.0
    assert st["new_tokens"] == 8.0         # short summary decode
    assert st["mean_prompt_tokens"] > 3 * 8

    ag = traffic_mod.get_profile("agentic-multiturn", page_size=8,
                                 requests=5)
    s = ag.sample(np.random.RandomState(0), vocab=128)
    assert s.shared_prefix is not None and len(s.shared_prefix) == 32
    for p in s.prompts:
        np.testing.assert_array_equal(p[:32], s.shared_prefix)
        assert 34 <= len(p) <= 40          # 32 shared + 2..8 turn tokens
    st = ag.prompt_stats()
    assert st["prefix_share_rate"] > 0.5   # the prefix IS the prompt


def test_sample_deterministic_and_prefixed():
    prof = traffic_mod.get_profile("shared-system-prompt", page_size=8,
                                   requests=5)
    a = prof.sample(np.random.RandomState(0), vocab=128)
    b = prof.sample(np.random.RandomState(0), vocab=128)
    assert len(a.prompts) == 5
    assert a.shared_prefix is not None and len(a.shared_prefix) == 16
    for pa, pb in zip(a.prompts, b.prompts):
        np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(pa[:16], a.shared_prefix)
        assert pa.dtype == np.int32


def test_prompt_stats_prefix_share():
    prof = traffic_mod.get_profile("shared-system-prompt", page_size=8,
                                   requests=6)
    st = prof.prompt_stats()
    assert st["mean_prompt_tokens"] == pytest.approx(16 + 10.0)
    assert st["p95_prompt_tokens"] == 16 + 16
    assert 0.0 < st["prefix_share_rate"] < 1.0
    assert traffic_mod.get_profile("smoke").prompt_stats()[
        "prefix_share_rate"] == 0.0


def test_mixed_profile_alternates_ranges():
    prof = traffic_mod.get_profile("mixed-length", page_size=8, requests=6)
    s = prof.sample(np.random.RandomState(0), vocab=128)
    for i, p in enumerate(s.prompts):
        if i % 2 == 0:
            assert 4 <= len(p) <= 9
        else:
            assert 25 <= len(p) <= 28  # chunk=24, +1..+4


def test_get_profile_passthrough_and_replace():
    prof = traffic_mod.smoke_profile(requests=3)
    assert traffic_mod.get_profile(prof) is prof
    assert traffic_mod.get_profile(prof, requests=9).requests == 9


# ---------------------------------------------------------------------------
# RecordedProfile: measured traffic from a reqlog export (ISSUE 15)


def _rec(sub_s, done_s, prompt, decode, cached=0, computed=None,
         chain=(), page=4, drafted=0, accepted=0):
    """A synthetic reqlog record with hand-controllable moments."""
    return {
        "submit_ns": int(sub_s * 1e9),
        "first_token_ns": int((sub_s + 0.1) * 1e9),
        "done_ns": int(done_s * 1e9),
        "prompt_tokens": prompt,
        "decode_tokens": decode,
        "cached_prefill_tokens": cached,
        "prefill_tokens": (prompt - cached if computed is None
                           else computed),
        "prefix_chain": list(chain),
        "page_size": page,
        "spec_draft_tokens": drafted,
        "spec_accepted_tokens": accepted,
    }


def test_recorded_profile_hand_computed_stats():
    """Every pricer input comes from the log — checked against the
    values computed by hand: prompt moments, measured prefix share,
    Little's-law concurrency, arrival process, realized acceptance."""
    records = [
        _rec(0.0, 2.0, prompt=8, decode=4, cached=0, drafted=6,
             accepted=3),
        _rec(1.0, 3.0, prompt=16, decode=8, cached=4, drafted=4,
             accepted=3),
    ]
    prof = traffic_mod.RecordedProfile(records, name="hand")
    assert prof.requests == 2
    assert prof.new_tokens == 6                       # round(mean(4, 8))
    assert prof.new_tokens_per_request == [4, 8]      # arrival order
    st = prof.prompt_stats()
    assert st["mean_prompt_tokens"] == pytest.approx(12.0)
    assert st["p95_prompt_tokens"] == 16.0            # nearest-rank
    # cache served 4 of the 4 + (8 + 12) looked-up prompt tokens
    assert st["prefix_share_rate"] == pytest.approx(4 / 24)
    # Little's law: residence (2 + 2) s over a 3 s makespan
    assert st["offered_concurrency"] == pytest.approx(4 / 3)
    ar = prof.arrival_stats()
    assert ar["requests"] == 2.0
    assert ar["makespan_s"] == pytest.approx(3.0)
    assert ar["arrival_rate_rps"] == pytest.approx(2 / 3)
    assert ar["mean_interarrival_s"] == pytest.approx(1.0)
    assert ar["p95_interarrival_s"] == pytest.approx(1.0)
    # acceptance: 6 of the 10 drafted tokens landed
    assert prof.measured_acceptance() == pytest.approx(0.6)
    # a log that never drafted measures None (search falls back)
    assert traffic_mod.RecordedProfile(
        [_rec(0.0, 1.0, prompt=4, decode=2)]).measured_acceptance() is None
    with pytest.raises(ValueError):
        traffic_mod.RecordedProfile([])


def test_recorded_profile_sample_resynthesizes_shared_prefix():
    """The records' hash chains prove the prompts shared their first
    page: sample() re-draws ONE shared prefix of that depth and opens
    every replayed prompt with it, deterministically in the seed."""
    records = [
        _rec(0.0, 1.0, prompt=8, decode=2, chain=("aa", "bb"), page=4),
        _rec(0.5, 1.5, prompt=9, decode=2, chain=("aa", "cc"), page=4),
    ]
    prof = traffic_mod.RecordedProfile(records)
    a = prof.sample(np.random.RandomState(3), vocab=64)
    b = prof.sample(np.random.RandomState(3), vocab=64)
    assert [len(p) for p in a.prompts] == [8, 9]      # recorded lengths
    assert a.shared_prefix is not None and len(a.shared_prefix) == 4
    for pa, pb in zip(a.prompts, b.prompts):
        np.testing.assert_array_equal(pa, pb)
        np.testing.assert_array_equal(pa[:4], a.shared_prefix)
    # divergent chains (or a single record) -> no synthetic prefix
    lone = traffic_mod.RecordedProfile(records[:1])
    assert lone.sample(np.random.RandomState(0), vocab=64) \
        .shared_prefix is None
    # the shared block always leaves a computed suffix: common depth 2
    # (8 tokens) against a 8-token shortest prompt caps at 7
    deep = traffic_mod.RecordedProfile([
        _rec(0.0, 1.0, prompt=8, decode=2, chain=("aa", "bb"), page=4),
        _rec(0.5, 1.5, prompt=12, decode=2, chain=("aa", "bb", "cc"),
             page=4),
    ])
    s = deep.sample(np.random.RandomState(0), vocab=64)
    assert len(s.shared_prefix) == 7
    assert [len(p) for p in s.prompts] == [8, 12]


def test_recorded_profile_from_reqlog_and_get_profile(tmp_path):
    from flexflow_tpu.obs import reqlog as reqlog_mod

    records = [_rec(0.0, 1.0, prompt=4, decode=2)]
    p = str(tmp_path / "run.jsonl")
    reqlog_mod.dump_jsonl(p, records)
    prof = traffic_mod.RecordedProfile.from_reqlog(p)
    assert prof.name == "replay:run.jsonl"
    assert prof.requests == 1
    # a RecordedProfile is measured, not parameterized: passthrough
    # works, overrides are refused
    assert traffic_mod.get_profile(prof) is prof
    with pytest.raises(ValueError, match="measured"):
        traffic_mod.get_profile(prof, requests=5)


# ---------------------------------------------------------------------------
# calibration freshness


def _report(age_s=0.0, stamped=True):
    now = 1_700_000_000.0
    rep = {"version": 2, "tick_scales": {}, "phases": {"decode": 1.5}}
    if stamped:
        rep["created_at_unix"] = now - age_s
        rep["created_at"] = "stamped"
    return rep, now


def test_load_calibration_fresh_accepted():
    rep, now = _report(age_s=3600.0)
    assert load_calibration(rep, now=now) is rep


def test_load_calibration_stale_refused():
    rep, now = _report(age_s=8 * 86400.0)
    assert load_calibration(rep, now=now) is None


def test_load_calibration_unstamped_refused():
    rep, now = _report(stamped=False)
    assert load_calibration(rep, now=now) is None


def test_load_calibration_max_age_override():
    rep, now = _report(age_s=8 * 86400.0)
    assert load_calibration(rep, max_age_s=30 * 86400.0, now=now) is rep


def test_calibration_report_schema_stamp():
    from flexflow_tpu.obs.calibrate import CALIBRATION_SCHEMA_VERSION

    assert CALIBRATION_SCHEMA_VERSION == 2


# ---------------------------------------------------------------------------
# the search itself (graph + cost — no compile, so it is fast)


@pytest.mark.parametrize("profile", ["smoke", "shared-system-prompt",
                                     "mixed-length"])
def test_search_beats_default(graph, profile):
    """The ISSUE-12 acceptance bar: on every named traffic profile the
    searched strategy must be STRICTLY better than the hand default on
    the simulated SLO objective."""
    res = search_serve_strategy(graph=graph, cost=_cost(), traffic=profile,
                                budget=120, seed=0, slots=4, max_len=128)
    assert res.best_objective < res.default_objective
    assert res.improvement > 0.0
    res.best.validate(max_len=128)


def test_search_deterministic_under_fixed_seed(graph):
    a = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                              budget=80, seed=3, slots=4, max_len=128)
    b = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                              budget=80, seed=3, slots=4, max_len=128)
    assert a.best == b.best
    assert a.best_objective == b.best_objective
    assert a.trials == b.trials


def test_search_consumes_calibration(graph):
    """A fresh report's scale factors must actually move the priced
    metrics: with decode 50x slower than analytic, the same default
    strategy prices at a worse objective and the result records the
    provenance."""
    rep = {"version": 2, "created_at_unix": time.time(),
           "created_at": "now", "tick_scales": {},
           "phases": {"decode": 50.0}}
    plain = search_serve_strategy(graph=graph, cost=_cost(),
                                  traffic="smoke", budget=40, seed=0,
                                  slots=4, max_len=128)
    cal = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                                budget=40, seed=0, slots=4, max_len=128,
                                calibration=rep)
    assert cal.calibration == {"used": True, "version": 2,
                               "created_at": "now", "shapes": 0}
    assert cal.default_objective > plain.default_objective


def test_search_refuses_stale_calibration(graph):
    rep = {"version": 2, "created_at_unix": time.time() - 30 * 86400,
           "created_at": "a month ago", "tick_scales": {},
           "phases": {"decode": 50.0}}
    res = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                                budget=40, seed=0, slots=4, max_len=128,
                                calibration=rep)
    assert res.calibration == {"used": False,
                               "reason": "stale-or-unstamped"}
    plain = search_serve_strategy(graph=graph, cost=_cost(),
                                  traffic="smoke", budget=40, seed=0,
                                  slots=4, max_len=128)
    assert res.default_objective == plain.default_objective


def test_search_replay_prices_measured_traffic(graph):
    """`servesearch search --replay` substance (ISSUE 15 acceptance):
    searching against a RecordedProfile returns a valid strategy whose
    pricer inputs come from the LOG — the result's stats/arrival/
    acceptance blocks equal the hand-computable measured values."""
    records = [
        _rec(0.0, 2.0, prompt=8, decode=4, drafted=8, accepted=6),
        _rec(1.0, 3.0, prompt=16, decode=8, cached=4, drafted=8,
             accepted=6),
    ]
    prof = traffic_mod.RecordedProfile(records, name="replay:test")
    res = search_serve_strategy(graph=graph, cost=_cost(), traffic=prof,
                                budget=80, seed=0, slots=4, max_len=128)
    res.best.validate(max_len=128)
    assert res.traffic == "replay:test"
    assert res.acceptance == {"rate": pytest.approx(0.75),
                              "source": "measured"}
    assert res.stats == prof.prompt_stats()
    assert res.stats["mean_prompt_tokens"] == pytest.approx(12.0)
    assert res.stats["prefix_share_rate"] == pytest.approx(4 / 24)
    assert res.arrival == prof.arrival_stats()
    assert res.arrival["arrival_rate_rps"] == pytest.approx(2 / 3)
    # provenance survives the persisted-result round trip
    back = ServeSearchResult.from_json(
        json.loads(json.dumps(res.to_json())))
    assert back.acceptance == res.acceptance
    assert back.stats == res.stats and back.arrival == res.arrival


def test_search_acceptance_source_default_and_explicit(graph):
    """Named profiles have no measured acceptance -> the prior, tagged
    'default'; a caller-supplied rate is tagged 'explicit'."""
    from flexflow_tpu.search.servesearch import DEFAULT_ACCEPTANCE_RATE

    res = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                                budget=40, seed=0, slots=4, max_len=128)
    assert res.acceptance == {"rate": DEFAULT_ACCEPTANCE_RATE,
                              "source": "default"}
    assert res.arrival is None            # closed-form profiles: no log
    res = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                                budget=40, seed=0, slots=4, max_len=128,
                                acceptance_rate=0.5)
    assert res.acceptance == {"rate": 0.5, "source": "explicit"}


def test_hbm_budget_steers_search(graph):
    """With a tight HBM budget the penalty term must push the winner's
    resident bytes to no more than the default's."""
    loose = search_serve_strategy(graph=graph, cost=_cost(),
                                  traffic="smoke", budget=120, seed=0,
                                  slots=4, max_len=128)
    tight_budget = loose.default_metrics["hbm_bytes"] * 0.9
    tight = search_serve_strategy(
        graph=graph, cost=_cost(), traffic="smoke", budget=120, seed=0,
        slots=4, max_len=128,
        objective=ServeObjective(hbm_budget_bytes=tight_budget))
    assert tight.best_metrics["hbm_bytes"] <= \
        tight.default_metrics["hbm_bytes"]
    assert tight.best_objective < tight.default_objective


def test_mesh_layouts_ride_existing_mcmc(graph):
    """layouts= + inner_budget>0 nests the EXISTING sharding search: the
    result carries one priced layout per candidate and the winner's mesh
    is one of them."""
    res = search_serve_strategy(
        graph=graph, cost=_cost(), traffic="smoke", budget=60, seed=0,
        slots=4, max_len=128,
        layouts=[{"data": 8}, {"data": 2, "model": 4}], inner_budget=10)
    assert len(res.layouts) == 2
    meshes = {tuple(sorted(lay["mesh"].items())) for lay in res.layouts}
    assert meshes == {(("data", 8),), (("data", 2), ("model", 4))}
    assert res.best.mesh in meshes
    for lay in res.layouts:
        assert lay["step_s"] > 0.0
        assert lay["kv_token_bytes"] > 0


def test_result_json_roundtrip(graph):
    res = search_serve_strategy(graph=graph, cost=_cost(), traffic="smoke",
                                budget=40, seed=0, slots=4, max_len=128)
    back = ServeSearchResult.from_json(
        json.loads(json.dumps(res.to_json())))
    assert back.best == res.best
    assert back.best_objective == res.best_objective
    assert back.objective == res.objective


# ---------------------------------------------------------------------------
# servability: a searched strategy drives a real server, token-identical


def _causal_lm():
    lcfg = LlamaConfig(vocab_size=256, dim=64, layers=2, heads=4,
                       kv_heads=2, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=11))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


def test_searched_strategy_serves_token_identical():
    """End to end: search on the compiled model (small budget), then
    serve the winner — greedy output must equal dense FFModel.generate,
    and the dict form (the tools/servesearch.py apply artifact) must
    load the same way."""
    ff, lcfg = _causal_lm()
    res = search_serve_strategy(ff, traffic="smoke", budget=40, seed=0,
                                slots=2, max_len=32)
    assert res.best_objective < res.default_objective
    res.best.validate(max_len=32)

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 6, 5)]
    want = [ff.generate(p[None, :], max_new_tokens=8)[0] for p in prompts]
    for strategy in (res.best, res.best.to_json()):
        server = ff.serve_generation(slots=2, max_len=32,
                                     serve_strategy=strategy)
        try:
            futs = [server.submit(p, max_new_tokens=8) for p in prompts]
            got = [f.result(timeout=600) for f in futs]
        finally:
            server.stop()
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)


def test_serve_strategy_rejects_explicit_speculate():
    ff, _ = _causal_lm()
    with pytest.raises(ValueError, match="speculation"):
        ff.serve_generation(slots=2, max_len=32,
                            serve_strategy=ServeStrategy(page_size=8),
                            speculate=SpecConfig(width=2, depth=2))
