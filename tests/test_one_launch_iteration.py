"""One launch an iteration (docs/paged.md "One launch an iteration").

An iteration of the paged server's loop that holds a prefill chunk AND
decoding slots runs ONE ragged launch: the chunk's pieces, then one q_len 1
item a decoding slot. These tests hold that launch to the two-launch order
it replaced (a test-only subclass that ticks the two apart): the same
tokens, greedy and sampled, on a K/V pool, an int8 pool and a latent pool
with shared experts; no launch shape outside the catalog; the spans the
benchmark's readers are written against; the counters the two ticks keep.
"""

import dataclasses
import itertools
import json
import os
import types

import numpy as np
import pytest

from benchmark.families import mistral4 as fam
from benchmark.readers import iteration_host, span_counter, tick_median, token_gap
from flexflow_tpu import FFConfig, FFModel, LossType, obs
from flexflow_tpu.analysis.shapecheck import (
    _packed_prefill_shapes,
    check_soundness,
    enumerate_catalog,
)
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.models.mistral4 import build_mistral4
from flexflow_tpu.paged.scheduler import PagedGenerationServer
from flexflow_tpu.serve_strategy import PREFILL_WINDOW_ROWS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
PAGE = 8
SLOTS = 4
CHUNK = 16


class TwoLaunchServer(PagedGenerationServer):
    """The order this PR replaced, for comparison only: the chunk's launch,
    then a (slots, 1) launch for the decoding slots."""

    def _host_tick(self, live, tr, ntr):
        pre, dec = self._split_live(live)
        if pre:
            self._prefill_tick(pre, tr, ntr)
        if dec:
            self._decode_tick(dec, tr, ntr)


def _llama():
    ff = FFModel(FFConfig(batch_size=1, seed=3, num_devices=1))
    build_llama(ff, LlamaConfig.tiny(vocab=VOCAB), seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _mistral4():
    """Latent attention from a latent pool, eight routed experts (two a
    token) plus a shared one through `expert_share`: a launch's pad rows
    must not be routed."""
    cfg = {
        "family": "mistral4", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 8, "n_shared_experts": 1,
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
        "experts_held": [0, 8], "published": {"n_routed_experts": 8},
    }
    ff = FFModel(FFConfig(batch_size=1, seed=5, num_devices=1))
    build_mistral4(ff, fam.program_config(cfg), batch_size=1, seq_len=8,
                   dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


# (graph, pool dtype): the pool families the one loop serves
FAMILIES = {"llama": ("llama", "auto"), "llama-int8": ("llama", "int8"),
            "mistral4": ("mistral4", "auto")}
# (prompt length, new tokens): prompts that end inside a chunk (3, 22, 41:
# chunks of 16), that end a chunk exactly (32), and requests whose first
# token is their last (30 and 32); six requests over four slots, so slots
# turn over while others decode
TRAFFIC = [(3, 6), (30, 1), (22, 9), (17, 5), (41, 7), (32, 1), (9, 3)]


@dataclasses.dataclass
class Served:
    tokens: list
    metrics: dict
    spans: list
    counters: dict
    catalog: dict
    observed: list


def _serve(ff, cls, kv_dtype, temperature):
    """Every request of TRAFFIC through a warmed server of class `cls`,
    submitted BEFORE the loop starts so that admission, and with it the
    order of the rng's splits, is the same run to run."""
    server = ff.serve_generation(
        paged=True, slots=SLOTS, max_len=64, page_size=PAGE,
        prefill_chunk=CHUNK, num_pages=40, kv_dtype=kv_dtype, seed=11,
        defer_start=True)
    server.__class__ = cls
    try:
        before = ff.executor.compile_tracker.compile_events_total
        catalog = server.warm_launch_shapes()
        rng = np.random.default_rng(0)
        futs = [server.submit(rng.integers(1, VOCAB, n, dtype=np.int32),
                              max_new_tokens=new, temperature=temperature)
                for n, new in TRAFFIC]
        rec = obs.enable()
        try:
            server.start()
            tokens = [np.asarray(f.result(timeout=600)) for f in futs]
        finally:
            obs.disable()
    finally:
        server.stop()       # folds the expert counters of the last launches
    metrics = server.metrics()
    counters = {
        "prefill_ticks": server.prefill_ticks,
        "steps": server._steps,
        "decode_tokens": int(server._h_tokens.sum),
        "decode_overlap_ticks": sorted(r["decode_overlap_ticks"]
                                       for r in metrics["requests"]),
        "prefill_observed": server._h_prefill.count,
        "ticks_observed": server._h_tick.count,
    }
    return Served(tokens, metrics, list(rec.events), counters, catalog,
                  ff.executor.compile_tracker.observed(since=before))


@pytest.fixture(scope="module")
def graphs():
    return {"llama": _llama(), "mistral4": _mistral4()}


@pytest.fixture(scope="module")
def served(graphs):
    """(family, "greedy" | "sampled") -> (one launch, two launches), each
    pair served once and shared by the cases below."""
    memo = {}

    def get(family, how):
        if (family, how) not in memo:
            graph, kv_dtype = FAMILIES[family]
            temp = 0.0 if how == "greedy" else 0.8
            memo[family, how] = tuple(
                _serve(graphs[graph], cls, kv_dtype, temp)
                for cls in (PagedGenerationServer, TwoLaunchServer))
        return memo[family, how]

    return get


CASES = list(itertools.product(FAMILIES, ("greedy", "sampled")))
CASE_IDS = [f"{f}-{h}" for f, h in CASES]


# ---------------------------------------------------------------------------
# (a) the sampled stream is the two-launch order's


@pytest.mark.parametrize("family,how", CASES, ids=CASE_IDS)
def test_tokens_are_the_two_launch_orders(served, family, how):
    one, two = served(family, how)
    assert one.metrics["launches"]["one_launch"] > 0
    assert two.metrics["launches"]["one_launch"] == 0
    for i, (w, g) in enumerate(zip(two.tokens, one.tokens)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
        assert len(g) == TRAFFIC[i][1]
    if how == "sampled":
        # the draws were real: greedy serves another stream
        greedy = served(family, "greedy")[0]
        assert any(not np.array_equal(a, b)
                   for a, b in zip(greedy.tokens, one.tokens))


@pytest.mark.parametrize("family", FAMILIES)
def test_every_iteration_with_both_kinds_launches_once(served, family):
    one, two = served(family, "greedy")
    m1, m2 = one.metrics["launches"], two.metrics["launches"]
    assert m1["iterations_with_both"] == m1["one_launch"] > 0
    assert m2 == {"iterations_with_both": m1["iterations_with_both"],
                  "one_launch": 0}
    n1 = sum(1 for e in one.spans if e[0] == "launch_dispatch")
    n2 = sum(1 for e in two.spans if e[0] == "launch_dispatch")
    assert n2 - n1 == m1["one_launch"]
    if family == "mistral4":
        # pad rows of the decode items were not routed: the launches
        # assigned as many (token, expert) pairs as there were live rows
        live = one.metrics["launch_rows"] - one.metrics["padded_rows"]
        assert live == two.metrics["launch_rows"] - two.metrics["padded_rows"]
        assert one.metrics["moe_assignments"] == \
            two.metrics["moe_assignments"] == 2 * 2 * live


# ---------------------------------------------------------------------------
# (b) no launch shape is added


def _planned_shape(remaining, d, chunk, cap):
    """(items, window) of `_prefill_tick`'s launch for mid-prefill slots
    with `remaining` prompt tokens each (in rotated order) and `d`
    decoding slots riding it."""
    budget, takes = chunk, []
    for left in remaining:
        if budget <= 0:
            break
        takes.append(min(budget, left))
        budget -= takes[-1]
    W = min(cap, max(takes))
    return sum(-(-t // W) for t in takes) + d, W


def _all_shapes(slots, chunk, cap):
    got = set()
    for k in range(1, slots + 1):
        for remaining in itertools.product(range(1, chunk + 2), repeat=k):
            for d in range(0, slots - k + 1):
                got.add(_planned_shape(remaining, d, chunk, cap))
    return got


@pytest.mark.parametrize("slots,chunk,cap", [
    (1, 5, 4), (2, 4, 4), (2, 9, 4), (3, 3, 2), (3, 6, 4), (3, 9, 4),
    (4, 5, 2), (4, 8, 8)])
def test_every_reachable_launch_is_in_the_catalog_and_no_other(slots, chunk,
                                                               cap):
    """Exhaustive at small sizes: every (k mid-prefill, d decoding, takes)
    plans a launch the catalog's packed family holds, and the family holds
    nothing that no plan reaches."""
    assert _all_shapes(slots, chunk, cap) == _packed_prefill_shapes(
        slots, chunk, cap)


@pytest.mark.parametrize("slots,chunk,ragged", [(8, 64, 71), (8, 256, 95),
                                                (16, 256, 159)])
def test_serving_sizes_plan_inside_the_catalog(slots, chunk, ragged):
    """At the benchmark's sizes (8 slots, chunks of 64 and 256) decode
    riders add NO shape to what the chunk's pieces alone reached (71 and
    95 ragged programs, as before); drawn plans, and the worst ones by
    construction, land inside."""
    family = _packed_prefill_shapes(slots, chunk) | {(slots, 1)}
    assert len(family) == ragged
    rng = np.random.default_rng(slots * chunk)
    plans = [([chunk], slots - 1), ([chunk + 7], slots - 1),
             ([1] * (slots - 1) + [chunk], 0), ([1, chunk], slots - 2)]
    for _ in range(4000):
        k = int(rng.integers(1, slots + 1))
        plans.append((list(rng.integers(1, 2 * chunk, k)),
                      int(rng.integers(0, slots - k + 1))))
    for remaining, d in plans:
        assert _planned_shape(remaining, d, chunk,
                              PREFILL_WINDOW_ROWS) in family
    assert max(B for B, W in family) == _planned_shape(
        [chunk], slots - 1, chunk, PREFILL_WINDOW_ROWS)[0]


@pytest.mark.parametrize("family,how", CASES, ids=CASE_IDS)
def test_mixed_run_compiles_nothing_outside_the_unchanged_catalog(
        served, family, how):
    one, _two = served(family, how)
    assert one.metrics["compile"]["steady_state_recompiles"] == 0
    assert one.catalog == enumerate_catalog(
        slots=SLOTS, max_len=64, page_size=PAGE, prefill_chunk=CHUNK,
        num_pages=40, kv_dtype=FAMILIES[family][1])
    assert check_soundness(one.catalog, one.observed) == []
    launched = {(a["items"], a["window"])
                for e in one.spans if e[0] == "launch_build"
                for a in [e[4]]}
    shapes = {tuple(s) for s in
              one.catalog["entries"]["ragged_step"]["shapes"]}
    assert launched <= shapes
    # decode rows rode windows wider than one row
    assert any(W > 1 and B > 1 for B, W in launched)


# ---------------------------------------------------------------------------
# (c) the spans the benchmark reads


def _iterations(spans):
    """The loop's iterations as lists of (name, attrs), split at
    `tick_prep`."""
    out = []
    for name, _t0, _dur, _tid, attrs in sorted(spans, key=lambda e: e[1]):
        if name == "tick_prep":
            out.append([])
        elif out:
            out[-1].append((name, attrs or {}))
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_iteration_with_both_keeps_its_spans(served, family):
    one, two = served(family, "greedy")
    both = [it for it in _iterations(one.spans)
            if any(n == "prefill_tick" for n, _ in it)
            and any(n == "decode_tick" for n, _ in it)]
    assert len(both) == one.metrics["launches"]["one_launch"]
    for it in both:
        names = [n for n, _ in it]
        assert names.count("launch_dispatch") == 1
        assert names.count("launch_build") == names.count("launch_h2d") == 1
        pre = dict(it)["prefill_tick"]
        assert (pre["decode_waiting"], pre["decode_rode"]) == (1, 1)
        dec = dict(it)["decode_tick"]
        # the tick names the requests whose rows it picked; its LAST
        # commit takes the launch BEFORE's tokens and names who gained
        # one there (tests/test_launch_ahead.py holds the two together)
        commits = [a for n, a in it if n == "commit"]
        assert dec["rids"] and "rids" in commits[-1]
        assert {"sample", "fetch"} <= set(names)
    # iterations with a chunk and nobody decoding say so
    alone = [dict(it)["prefill_tick"] for it in _iterations(one.spans)
             if any(n == "prefill_tick" for n, _ in it)
             and not any(n == "decode_tick" for n, _ in it)]
    assert alone and all(
        (a["decode_waiting"], a["decode_rode"]) == (0, 0) for a in alone)
    # the two-launch order waits and does not ride
    apart = [a for e in two.spans if e[0] == "prefill_tick"
             for a in [e[4]] if a["decode_waiting"]]
    assert apart and all(a["decode_rode"] == 0 for a in apart)


READERS = {
    "step_ms.prefill": lambda run: tick_median.read(run, "with_prefill"),
    "step_ms.decode": lambda run: tick_median.read(run, "decode_only"),
    "host_ms.prefill": lambda run: iteration_host.read(run, "with_prefill"),
    "token_gap_p99": lambda run: token_gap.read(run, 99),
    # PR 37's two, one reader: `ahead` over `launches` of the launch spans
    # (tests/test_launch_ahead.py holds them to the server's counters and
    # leaves them out at a program without the keys)
    "launch_ahead_share.prefill": lambda run: span_counter.read(
        run, "launch_dispatch", "ahead", over="launches", scale=100),
    "launch_ahead_share.decode": lambda run: span_counter.read(
        run, "launch_dispatch", "ahead", over="launches", scale=100),
}


@pytest.mark.parametrize("metric", READERS)
def test_benchmark_readers_find_their_spans(served, metric):
    one, _two = served("llama", "greedy")
    value = READERS[metric](types.SimpleNamespace(spans=one.spans))
    assert value is not None and value > 0.0


def test_one_launch_share_reads_100_and_is_left_out_at_the_parent(served):
    one, two = served("llama", "greedy")
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "one_launch_share.json")) as f:
        metric = json.load(f)
    reader = dict(metric["reader"])
    assert reader.pop("name") == "span_counter"
    assert span_counter.read(types.SimpleNamespace(spans=one.spans),
                             **reader) == 100.0
    assert span_counter.read(types.SimpleNamespace(spans=two.spans),
                             **reader) == 0.0
    # a program whose spans lack the key (the parent): nothing to read
    bare = [(n, t, d, tid, {k: v for k, v in (a or {}).items()
                            if not k.startswith("decode_")})
            for n, t, d, tid, a in one.spans]
    assert span_counter.read(types.SimpleNamespace(spans=bare),
                             **reader) is None
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "one_launch_share"]
    # the two cells PR 34 gave it; later cells are appended (PR 36's)
    assert len(entry) == 1 and entry[0]["workloads"][:2] == [
        "mistral-7b-serve1.longdoc-backlog",
        "mistral-small-4-serve1.longctx-backlog"]
    assert {k: v for k, v in entry[0].items() if k != "workloads"} == (
        {k: metric[k] for k in ("name", "unit", "source", "layer", "moves")}
        | {"better": "higher"})


# ---------------------------------------------------------------------------
# (d) the counters keep what they count


@pytest.mark.parametrize("family,how", CASES, ids=CASE_IDS)
def test_counters_equal_the_two_launch_orders(served, family, how):
    one, two = served(family, how)
    assert one.counters == two.counters
    assert one.counters["steps"] == one.counters["ticks_observed"]
    assert one.counters["decode_tokens"] == sum(
        new - 1 for _n, new in TRAFFIC)
    # the rows the launches carried: a rider pads its window where it
    # padded a share of the (slots, 1) launch
    live = one.metrics["launch_rows"] - one.metrics["padded_rows"]
    assert live == two.metrics["launch_rows"] - two.metrics["padded_rows"]
