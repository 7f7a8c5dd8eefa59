"""One page pool, one owner (docs/paged.md "Who owns the pool").

Every serving program that takes the pools and returns them CONSUMES the
buffers it is given (donate_argnums) and writes K/V where it lies; the
server's `self._caches` is the one live reference, rebound at every call.
These tests hold the executor to the alias (lowered text, buffer
addresses, live arrays after warm-up), the served tokens to what the
undonated programs give, and every reader of a pool that is not the loop
to reading it while the loop is launching: a use after donation raises
`Array has been deleted` on the CPU backend too.

Each case runs on a K/V-pool graph (tiny llama: `"k"`/`"v"` entries, with
an int8 pool's scale sidecar where the program has one) and on a
latent-pool graph (tiny mistral4: one `"c"` entry a node), and the ragged
step also on a graph with two classes of pages (tiny mellum2) and on one
with a state a slot beside its pages (tiny ling3). Those three refuse
speculation and the int8 pool by name, so their cases are the ragged step's.
"""

import dataclasses
import gc
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_ling3 as ling3_tiny
import test_mellum2 as mellum2_tiny
from benchmark.families import mistral4 as fam
from benchmark.readers import span_counter
from flexflow_tpu import FFConfig, FFModel, LossType, obs
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.models.mistral4 import build_mistral4
from flexflow_tpu.runtime.executor import LAUNCH_STATS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
PAGE = 8


def _llama():
    ff = FFModel(FFConfig(batch_size=1, seed=3, num_devices=1))
    build_llama(ff, LlamaConfig.tiny(vocab=VOCAB), seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _mistral4():
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


@pytest.fixture(scope="module")
def graphs():
    return {"llama": _llama(), "mistral4": _mistral4(),
            "mellum2": mellum2_tiny.build(mellum2_tiny.config()),
            "ling3": ling3_tiny.build(ling3_tiny.config())}


# (graph, entry, pool dtype): every program that takes the pools and
# returns them, on every pool family it serves
PROGRAMS = [
    ("llama", "ragged_step", None),
    ("llama", "paged_commit", None),
    ("llama", "ragged_step", "int8"),
    ("llama", "paged_commit", "int8"),
    ("mistral4", "ragged_step", None),
    ("mellum2", "ragged_step", None),
    ("ling3", "ragged_step", None),
]
IDS = [f"{g}-{e}" + (f"-{d}" if d else "") for g, e, d in PROGRAMS]
SLOTS, COLS = 2, 3


def _program(ff, entry, kv_dtype):
    """(fn, args before the pools, a fresh pool, args after it, keyword
    args, where the pools are among the outputs) for one call of `entry`:
    two slots, the first with a live row, tables over pages 1.. (a table a
    class where the graph has window layers, the items' slots where it has
    state layers)."""
    from flexflow_tpu.paged.quant import resolve_kv_dtype

    ex = ff.executor
    tr, ntr = ff._params
    pages = 1 + SLOTS * COLS
    two = ex.page_classes() is not None
    caches = ex.init_paged_kv_cache(
        pages, PAGE, dtype=resolve_kv_dtype(kv_dtype),
        num_pages_window=pages if two else None, slots=SLOTS)
    tables = 1 + np.arange(SLOTS * COLS, dtype=np.int32).reshape(SLOTS, COLS)
    tables = jnp.asarray(np.stack([tables, tables]) if two else tables)
    z = jnp.zeros((SLOTS,), jnp.int32)
    if entry == "ragged_step":
        W = 4
        deps = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (SLOTS, W))
        anc = jnp.broadcast_to(jnp.tril(jnp.ones((W, W), jnp.bool_)),
                               (SLOTS, W, W))
        kw = ({"state_slots": jnp.arange(SLOTS, dtype=jnp.int32)}
              if ex.state_layers() else {})
        return (ex.ragged_step_fn(), (tr, ntr), caches,
                (tables, z, jnp.asarray(np.array([3, 0], np.int32)), deps,
                 anc, jnp.ones((SLOTS, W), jnp.int32)), kw,
                lambda out: out[1])
    assert entry == "paged_commit"
    rows = jnp.asarray(np.array([[0, 1], [0, 0]], np.int32))
    return (ex.paged_commit_fn(), (), caches, (tables, rows + 2, rows), {},
            lambda out: out)


@pytest.mark.parametrize("graph,entry,kv_dtype", PROGRAMS, ids=IDS)
def test_lowered_program_aliases_every_pool_leaf(graphs, graph, entry,
                                                 kv_dtype):
    """(a) the lowering marks EVERY pool leaf (an int8 pool's scale
    sidecar leaves among them) as an output's buffer, and no other
    argument: the weights stay the caller's."""
    fn, head, caches, tail, kw, _pools = _program(graphs[graph], entry,
                                                  kv_dtype)
    leaves = len(jax.tree.leaves(caches))
    if graph != "ling3":    # its nodes differ: states, a latent pool
        assert leaves == len(caches) * {
            None: 1 if graph == "mistral4" else 2, "int8": 4}[kv_dtype]
    text = fn.lower(*head, caches, *tail, **kw).as_text()
    assert text.count("tf.aliasing_output") == leaves
    assert "jax.buffer_donor" not in text   # a donation with no output


@pytest.mark.parametrize("graph,entry,kv_dtype", PROGRAMS, ids=IDS)
def test_call_consumes_the_pool_and_writes_it_in_place(graphs, graph, entry,
                                                       kv_dtype):
    """(b) after one call the pool passed in is gone and each returned
    leaf lies in the device buffer its input had."""
    fn, head, caches, tail, kw, pools = _program(graphs[graph], entry,
                                                 kv_dtype)
    given = jax.tree.leaves(caches)
    where = [leaf.unsafe_buffer_pointer() for leaf in given]
    out = pools(fn(*head, caches, *tail, **kw))
    out.pop(LAUNCH_STATS, None)
    jax.block_until_ready(out)
    assert all(leaf.is_deleted() for leaf in given)
    assert [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(out)] == where
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(given[0])


def _pool_shaped(shape):
    gc.collect()
    return [a for a in jax.live_arrays()
            if a.shape == shape and not a.is_deleted()]


@pytest.mark.parametrize("graph", ["llama", "mistral4"])
def test_warm_up_leaves_one_pool_and_compiles_a_shape_once(graphs, graph):
    """(c) warm_launch_shapes() threads ONE pool through every shape:
    when it returns, the only pool-shaped buffers alive are the
    server's, each launch shape compiled once, its record says every leaf
    was written in place (and every traced launch carries the pair, which
    the benchmark's `pool_in_place_share` reads as 100), and serving adds
    no signature to the jit cache."""
    ff = graphs[graph]
    pages = 23                  # a pool shape no other test of this file has
    server = ff.serve_generation(paged=True, slots=2, max_len=40,
                                 page_size=PAGE, prefill_chunk=16,
                                 num_pages=pages, defer_start=True)
    try:
        own = jax.tree.leaves(server._caches)
        shape = own[0].shape
        assert shape[:2] == (pages, PAGE)
        before = ff.executor.compile_tracker.compile_events_total
        catalog = server.warm_launch_shapes()
        shapes = [tuple(s) for s in
                  catalog["entries"]["ragged_step"]["shapes"]]
        alive = _pool_shaped(shape)
        assert len(alive) == len(own)
        assert {id(a) for a in alive} == {id(a) for a in own}
        events = [ev["shape"] for ev in
                  ff.executor.compile_tracker.observed(since=before)
                  if ev["entry"] == "ragged_step"]
        assert sorted(events) == sorted(shapes)      # each shape, once
        assert server._step._cache_size() >= len(shapes)
        signatures = server._step._cache_size()
        for s in shapes:
            assert server._pool_alias[s] == (
                len(own), len(own))
        rec = obs.enable()
        try:
            server.start()
            rng = np.random.default_rng(0)
            for f in [server.submit(
                    rng.integers(0, VOCAB, n, dtype=np.int32), 4)
                    for n in (3, 14, 22, 31)]:
                f.result(timeout=300)
        finally:
            obs.disable()
        # every traced launch says so, and the benchmark's metric reads it
        launches = [e[4] for e in rec.events if e[0] == "launch_dispatch"]
        assert launches and all(
            (a["pools_passed"], a["pools_in_place"]) == (len(own), len(own))
            for a in launches)
        with open(os.path.join(REPO, "benchmark", "metrics",
                               "pool_in_place_share.json")) as f:
            reader = json.load(f)["reader"]
        assert reader.pop("name") == "span_counter"
        assert span_counter.read(types.SimpleNamespace(spans=rec.events),
                                 **reader) == 100.0
        assert server._step._cache_size() == signatures
        assert server.metrics()["compile"]["steady_state_recompiles"] == 0
        assert len(_pool_shaped(shape)) == len(own)
    finally:
        server.stop()


def _serve(ff, prompts, new, **kw):
    server = ff.serve_generation(paged=True, **kw)
    try:
        futs = [server.submit(p, max_new_tokens=new) for p in prompts]
        got = [np.asarray(f.result(timeout=300)) for f in futs]
        return got, server.metrics()
    finally:
        server.stop()


@pytest.mark.parametrize("graph", ["llama", "mistral4"])
def test_served_tokens_do_not_depend_on_the_alias(graphs, graph,
                                                  monkeypatch):
    """(d) chunked prefill, decode and a preempted request serve the same
    tokens through the donated step as through the same step jitted
    without donation (the parent's program)."""
    ff = graphs[graph]
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (21, 9, 26, 13)]
    # prompts of 2-4 pages in chunks of 8 rows; two slots want up to 10
    # pages at their deepest and the pool holds 6, so the younger request
    # is preempted and resumes
    kw = dict(slots=2, max_len=40, page_size=PAGE, prefill_chunk=8,
              num_pages=7)
    got, m = _serve(ff, prompts, 8, **kw)
    assert m["preemptions"] > 0 and m["prefill_ticks"] > len(prompts)
    donated = ff.executor.ragged_step_fn()
    monkeypatch.setattr(ff.executor, "_ragged_step_fn",
                        jax.jit(donated.__wrapped__))
    want, m0 = _serve(ff, prompts, 8, **kw)
    assert m0["preemptions"] > 0
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")


# ---------------------------------------------------------------------------
# readers of a pool that are not the loop, while the loop is launching


class _Scraper:
    """Another thread reading the server as an operator's scrape does
    (metrics() sizes itself from the pool's leaves), for as long as the
    block runs; an error there fails the test."""

    def __init__(self, read):
        self.read = read
        self.reads = 0
        self.error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                self.read()
            except Exception as e:      # reported by __exit__, below
                self.error = e
                return
            self.reads += 1
            time.sleep(0.002)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=60)
        assert not self._thread.is_alive()
        if self.error is not None:
            raise self.error
        assert self.reads > 0


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n, dtype=np.int32) for n in lens]


def _tier(ff):
    """Host tier spill and fetch: a pool too small for its traffic spills
    evicted and preempted pages through `_tier_read_page` and fetches
    them back through `_tier_write_page` between the launches of the
    other slot."""
    from flexflow_tpu.disagg.host_tier import HostTier

    prompts = _prompts(2, (13, 9, 12, 7))
    prompts += prompts[:2]      # their prefixes have left the pool by then
    want = [ff.generate(p[None, :], max_new_tokens=8)[0] for p in prompts]
    # two slots want up to 11 pages at their deepest; the pool holds 6
    server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                 page_size=4, num_pages=7,
                                 host_tier=HostTier(64))
    try:
        with _Scraper(server.metrics):
            futs = [server.submit(p, max_new_tokens=8) for p in prompts]
            got = [f.result(timeout=300) for f in futs]
        m = server.metrics()
        server.pool.check_invariants(owners={})
    finally:
        server.stop()
    assert m["host_tier"]["spilled_pages"] > 0
    assert m["host_tier"]["fetched_pages"] > 0
    return want, got


def _handoff(ff):
    """Handoff between two servers: the prefill worker's pages reach the
    decode worker's pool through the shared tier while both loops
    launch."""
    from flexflow_tpu.disagg.workers import DisaggPair

    prompts = _prompts(3, (5, 11, 8, 6, 9, 7))
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    pair = DisaggPair(ff, tier_pages=64, page_size=4, num_pages=24,
                      max_len=32, slots=2)
    try:
        with _Scraper(pair.metrics):
            futs = [pair.submit(p, max_new_tokens=5) for p in prompts]
            got = [f.result(timeout=300) for f in futs]
        assert pair.handoffs == len(prompts)
        pair.decode.pool.check_invariants(owners={})
    finally:
        pair.stop()
    return want, got


def _swap(ff):
    """Drain-and-swap: the successor adopts the predecessor's pool
    (`self._caches = old._caches`) in the middle of live submits and
    launches on it; the predecessor, stopped, still answers a scrape
    (shapes survive the donation of its buffers, contents do not)."""
    from flexflow_tpu.search.servesearch import ServeStrategy
    from flexflow_tpu.serving_autopilot import ServingAutopilot

    prompts = _prompts(19, (3, 5, 4))
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    ap = ServingAutopilot(ff, ServeStrategy(page_size=8, prefill_chunk=32),
                          slots=2, max_len=32)
    old = ap.server
    try:
        alt = dataclasses.replace(ap.strategy, prefill_chunk=16)
        swap = {}
        worker = threading.Thread(
            target=lambda: swap.update(ap.swap_to(alt)))
        futs = []
        with _Scraper(ap.metrics):
            worker.start()
            i = 0
            while worker.is_alive():
                if sum(1 for _, f in futs if not f.done()) < 4:
                    futs.append((i % 3, ap.submit(prompts[i % 3],
                                                  max_new_tokens=6)))
                    i += 1
                else:
                    time.sleep(0.02)
            worker.join(timeout=300)
            assert not worker.is_alive()
            futs += [(j, ap.submit(prompts[j], max_new_tokens=6))
                     for j in range(3)]
            got = [f.result(timeout=300) for _, f in futs]
        assert swap["to"] == alt.fingerprint() and ap.server is not old
        if swap["pool_adopted"]:
            # one set of buffers, the successor's: the predecessor's
            # reference names buffers later launches consumed
            assert ap.server.pool is old.pool
        assert old.metrics()["kv_cache_dtype"] == \
            ap.server.metrics()["kv_cache_dtype"]
        ap.server.pool.check_invariants(owners={})
    finally:
        ap.stop()
    return [want[k] for k, _ in futs], got


def _canary(ff):
    """Canary window: `_shadow_snapshot` reads the live pool into a
    second, separately owned float32 pool, which then rides every launch
    through the same consuming step until its request leaves."""
    prompts = _prompts(9, (9, 4, 11, 6))
    kw = dict(slots=2, max_len=32, page_size=4, kv_dtype="int8")
    want, _ = _serve(ff, prompts, 6, **kw)
    server = ff.serve_generation(paged=True, kv_quant_canary=1, **kw)
    try:
        with _Scraper(server.metrics):
            futs = [server.submit(p, max_new_tokens=6) for p in prompts]
            got = [f.result(timeout=300) for f in futs]
        can = server.metrics()["kv_quant_canary"]
    finally:
        server.stop()
    assert can["windows"] >= 2 and not can["window_open"]
    return want, got


@pytest.mark.parametrize("reader", [_tier, _handoff, _swap, _canary],
                         ids=lambda f: f.__name__.strip("_"))
def test_reader_outside_the_loop_while_it_launches(graphs, reader):
    """Each reader of a pool that is not the serving loop, exercised
    while launches consume and rebind the pool under it and a scraper
    thread reads the server: a buffer held across a launch raises
    `Array has been deleted` and fails here, not at a user."""
    want, got = reader(graphs["llama"])
    assert len(want) == len(got) > 0
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=f"request {i}")
