"""Launch ahead (docs/paged.md "Launch ahead").

The paged server's loop dispatches iteration N + 1's launch BEFORE it takes
iteration N's picks: a decode row reads its token id from the device, the
host learns it a launch late. These tests hold that pipeline to the serial
order, which is the same code with the pipeline drained after every
iteration (a test-only subclass that fences there): the same tokens, greedy
and at a seeded temperature, with and without an EOS, for the four model
families the benchmark serves (a K/V pool, a latent pool with experts, two
classes of pages, a state a slot beside a latent pool); every launch
accounted for; every fence where it is listed; no page freed under a
launch that names it; the spans the benchmark's readers are written
against.
"""

import json
import os
import time
import types

import numpy as np
import pytest

import test_ling3 as ling3_tiny
import test_mellum2 as mellum2_tiny
import test_one_launch_iteration as one_launch
from benchmark import tickspans
from benchmark.readers import span_counter, token_gap
from flexflow_tpu import obs
from flexflow_tpu.disagg.workers import DisaggPair
from flexflow_tpu.paged.scheduler import PagedGenerationServer
from flexflow_tpu.spec import SpecConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
PAGE = 8
SLOTS = 4
CHUNK = 16


class DrainedServer(PagedGenerationServer):
    """The serial order: every iteration ends with the fence, so the host
    has every token before it prepares the next launch."""

    def _host_tick(self, live, tr, ntr):
        super()._host_tick(live, tr, ntr)
        self._retire("drained")


# family -> (graph, what its server is built with)
FAMILIES = {
    "mistral-7b": ("llama", {}),
    "mistral-small-4": ("mistral4", {}),
    "mellum2": ("mellum2", {"prefix_cache": False}),
    "ling-3-flash": ("ling3", {"prefix_cache": False}),
}
# (prompt length, new tokens): seven requests over four slots, so slots
# turn over while others decode; prompts that end inside a chunk, that end
# one exactly, and requests whose first token is their last
BACKLOG = [(3, 6), (30, 1), (22, 9), (17, 5), (41, 7), (32, 1), (9, 3)]
# four requests whose prompts share the first launch's chunk budget: every
# later launch holds decode rows only (an EOS learnt late then never leaves
# an iteration that the serial order would not have had)
DECODERS = [(3, 12), (4, 12), (3, 12), (4, 12)]


@pytest.fixture(scope="module")
def graphs():
    return {"llama": one_launch._llama(), "mistral4": one_launch._mistral4(),
            "mellum2": mellum2_tiny.build(mellum2_tiny.config()),
            "ling3": ling3_tiny.build(ling3_tiny.config())}


def _watch_pages(server):
    """Wrap `_launch` and the full class's `free`: at every launch the
    invariant catalog holds (after it; before it where the graph has state
    layers, whose account a launch advances before the tick advances the
    requests'), and no page that a launch IN FLIGHT names (dispatched, not
    known to be done) ever leaves its owners."""
    named, real_launch, real_free = {}, server._launch, server.pool.free
    stateful = bool(server._state_keys)

    def launch(items, window, tr, ntr):
        if stateful:
            server._check_invariants()
        out = real_launch(items, window, tr, ntr)
        tables = server._tables
        # an item without rows (an idle slot of the decode launch) does
        # no work: its table row is not walked
        named[server.launches] = {
            int(p) for s, _pos, toks, *_ in items if len(toks)
            for p in tables[s] if p}
        if not stateful:
            server._check_invariants()
        return out

    def free(pages):
        real_free(pages)
        for seq in range(server._synced + 1, server.launches + 1):
            gone = {p for p in named.get(seq, ())
                    if p not in server.pool._refs}
            assert not gone, (seq, sorted(gone), "freed under a launch")

    server._launch = launch
    server.pool.free = free


def _serve(ff, cls, traffic, *, temperature=0.0, eos_id=None, trace=False,
           watch=False, seed=0, **server_kw):
    """`traffic` through a warmed server of class `cls`, submitted BEFORE
    the loop starts so that admission, and with it the order of the rng's
    splits, is the same run to run."""
    kw = dict(paged=True, slots=SLOTS, max_len=64, page_size=PAGE,
              prefill_chunk=CHUNK, seed=11, eos_id=eos_id, defer_start=True)
    kw.update(server_kw)
    server = ff.serve_generation(**kw)
    if type(server) is PagedGenerationServer:
        server.__class__ = cls
    try:
        server.warm_launch_shapes()
        if watch:
            _watch_pages(server)
        rng = np.random.default_rng(seed)
        futs = [server.submit(rng.integers(1, VOCAB, n, dtype=np.int32),
                              max_new_tokens=new, temperature=temperature)
                for n, new in traffic]
        rec = obs.enable() if trace else None
        try:
            server.start()
            tokens = [np.asarray(f.result(timeout=600)) for f in futs]
        finally:
            if trace:
                obs.disable()
    finally:
        server.stop()
    return types.SimpleNamespace(
        tokens=tokens, metrics=server.metrics(), server=server,
        spans=list(rec.events) if rec else [])


def _accounted(m):
    """Every launch is ahead, or charged to the fence that drained the
    pipeline before it (`start`: the first)."""
    assert (m["launches_ahead"] + sum(m["launches_drained"].values())
            == m["launches_dispatched"]), m
    assert set(m["launches_drained"]) <= set(m["fences"]) | {"start"}, m


# ---------------------------------------------------------------------------
# (a) the tokens are the serial order's


@pytest.fixture(scope="module")
def backlog(graphs):
    """(family, "greedy" | "sampled") -> (ahead, drained) over BACKLOG."""
    memo = {}

    def get(family, how):
        if (family, how) not in memo:
            graph, kw = FAMILIES[family]
            temp = 0.0 if how == "greedy" else 0.8
            memo[family, how] = tuple(
                _serve(graphs[graph], cls, BACKLOG, temperature=temp,
                       watch=True, trace=cls is PagedGenerationServer, **kw)
                for cls in (PagedGenerationServer, DrainedServer))
        return memo[family, how]

    return get


@pytest.mark.parametrize("how", ["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_are_the_drained_orders(backlog, family, how):
    ahead, drained = backlog(family, how)
    for i, (w, g) in enumerate(zip(drained.tokens, ahead.tokens)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
        assert len(g) == BACKLOG[i][1]
    if how == "sampled":
        greedy = backlog(family, "greedy")[0]
        assert any(not np.array_equal(a, b)
                   for a, b in zip(greedy.tokens, ahead.tokens))
    # the same launches in the same order: a request whose last token is
    # in flight gives its slot up at the dispatch, as the serial order does
    for key in ("launches_dispatched", "launch_rows", "padded_rows",
                "prefill_ticks", "decode_steps", "preemptions"):
        assert ahead.metrics[key] == drained.metrics[key], key


@pytest.mark.parametrize("family", FAMILIES)
def test_on_a_backlog_every_launch_but_the_first_is_ahead(backlog, family):
    ahead, drained = backlog(family, "greedy")
    m = ahead.metrics
    _accounted(m)
    assert m["launches_drained"] == {"start": 1}
    assert m["launches_ahead"] == m["launches_dispatched"] - 1 > 10
    # what ended the run: the last tokens were in flight with nothing to
    # launch (`idle`); the loop slept on none of them
    assert m["fences"] == {"idle": 1} and m["late_stop_rows"] == 0
    d = drained.metrics
    _accounted(d)
    assert d["fences"]["drained"] == d["launches_drained"]["drained"] + 1
    assert not ahead.server._flight and not drained.server._flight
    for s in (ahead.server, drained.server):
        assert s.pool.pages_in_use == 0
        assert all(r.unseen == 0 for r in s._active if r is not None)


# ---------------------------------------------------------------------------
# (b) a stop the host learns late costs one row, never a token


@pytest.mark.parametrize("how", ["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_an_eos_a_launch_late_emits_nothing_after_it(graphs, family, how):
    graph, kw = FAMILIES[family]
    temp = 0.0 if how == "greedy" else 0.8
    free = _serve(graphs[graph], DrainedServer, DECODERS, temperature=temp,
                  **kw)
    # an EOS that some request emits mid-stream and none emits first
    firsts = {int(t[0]) for t in free.tokens}
    eos = next(int(tok) for t in free.tokens for tok in t[2:-2]
               if int(tok) not in firsts)
    ahead, drained = (
        _serve(graphs[graph], cls, DECODERS, temperature=temp, eos_id=eos,
               watch=True, **kw)
        for cls in (PagedGenerationServer, DrainedServer))
    stopped = 0
    for i, (w, g, f) in enumerate(zip(drained.tokens, ahead.tokens,
                                      free.tokens)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
        if eos in f.tolist():
            cut = f.tolist().index(eos) + 1
            np.testing.assert_array_equal(g, f[:cut])
            stopped += cut < len(f)
    assert stopped >= 1
    # each request that stopped early had ONE row in the launch after,
    # which emitted nothing; the serial order never dispatched it
    assert ahead.metrics["late_stop_rows"] == stopped
    assert drained.metrics["late_stop_rows"] == 0
    _accounted(ahead.metrics)
    assert ahead.server.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# (c) each fence fires where it is listed


@pytest.mark.parametrize("what", ["preempt", "defrag"])
@pytest.mark.parametrize("family", ["mistral-7b", "mellum2", "ling-3-flash"])
def test_preemption_and_defrag_fence_first(graphs, family, what):
    """`preempt`: a full class too small for two long requests (the
    younger is preempted and recomputed). `defrag`: a compaction asked for
    every third launch. Both take what is in flight first, and the tokens
    are the serial order's."""
    graph, kw = FAMILIES[family]
    ff = graphs[graph]
    out = {}
    for cls in (PagedGenerationServer, DrainedServer):
        server = ff.serve_generation(
            paged=True, slots=2, max_len=96, page_size=PAGE,
            prefill_chunk=16, num_pages=14 if what == "preempt" else 30,
            defer_start=True, **kw)
        server.__class__ = cls
        _watch_pages(server)
        watched, n = server._launch, [0]

        def launch(*a, server=server, watched=watched, n=n):
            n[0] += 1
            if what == "defrag" and n[0] % 3 == 0:
                server.request_defrag()
            return watched(*a)

        server._launch = launch
        rng = np.random.default_rng(8)
        try:
            futs = [server.submit(rng.integers(0, VOCAB, k, dtype=np.int32),
                                  20) for k in (44, 46)]
            server.start()
            out[cls] = [np.asarray(f.result(timeout=600)) for f in futs]
        finally:
            server.stop()
        m = server.metrics()
        _accounted(m)
        assert (m["preemptions"] if what == "preempt" else m["defrags"]) >= 1
        if cls is PagedGenerationServer:
            assert m["fences"][what] >= 1, m["fences"]
            assert m["launches_drained"][what] >= 1
    for w, g in zip(out[DrainedServer], out[PagedGenerationServer]):
        np.testing.assert_array_equal(w, g)


def _until(cond, what, timeout=120.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, what
        time.sleep(0.002)


def test_stop_takes_what_is_in_flight(graphs):
    ff = graphs["llama"]
    server = ff.serve_generation(paged=True, slots=2, max_len=64,
                                 page_size=PAGE, prefill_chunk=CHUNK)
    fut = server.submit(np.arange(1, 12, dtype=np.int32), 50)
    _until(lambda: server.decode_steps >= 5, "the request never decoded")
    server.stop()
    m = server.metrics()
    assert m["fences"].get("stop") == 1 and not server._flight
    assert fut.cancelled() or len(fut.result()) == 50
    _accounted(m)
    server._check_invariants()
    assert server.pool.pages_in_use == 0


def test_drain_and_swap_carries_every_token_over(graphs):
    """detach_for_swap() stops the loop: it takes the picks in flight
    (`swap`), so a carried request holds every token its launches emitted
    and the successor resumes the greedy stream where it stood."""
    ff = graphs["llama"]
    whole = _serve(ff, DrainedServer, [(19, 24)], seed=3)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, VOCAB, 19, dtype=np.int32)
    kw = dict(paged=True, slots=SLOTS, max_len=64, page_size=PAGE,
              prefill_chunk=CHUNK, seed=11)
    old = ff.serve_generation(**kw)
    fut = old.submit(prompt, 24)
    _until(lambda: old.decode_steps >= 6, "the request never decoded")
    carried = old.detach_for_swap()
    assert old.metrics()["fences"].get("swap") == 1 and not old._flight
    (req,) = carried
    assert req.unseen == 0 and 6 <= len(req.tokens) < 24
    old._check_invariants()
    new = ff.serve_generation(defer_start=True, **kw)
    try:
        assert new.adopt_pool_from(old)
        new.absorb_requests(carried)
        new.start()
        np.testing.assert_array_equal(fut.result(timeout=600),
                                      whole.tokens[0])
    finally:
        new.stop()
        old.stop()
    _accounted(new.metrics())


def test_the_speculative_server_drafts_on_the_hosts_truth(graphs):
    ff = graphs["llama"]
    plain = _serve(ff, DrainedServer, BACKLOG)
    spec = _serve(ff, PagedGenerationServer, BACKLOG, watch=True,
                  speculate=SpecConfig(width=2, depth=2))
    for w, g in zip(plain.tokens, spec.tokens):
        np.testing.assert_array_equal(w, g)
    m = spec.metrics
    # a finishing chunk's first token is in flight when the verify tick
    # is entered: it fences, and launches drained
    assert m["fences"]["spec"] >= 1 and m["launches_drained"]["spec"] >= 1
    assert m["speculative"]["steps"] > 0
    _accounted(m)


def test_the_prefill_worker_hands_off_behind_a_fence(graphs):
    ff = graphs["llama"]
    plain = _serve(ff, DrainedServer, BACKLOG)
    pair = DisaggPair(ff, slots=SLOTS, max_len=64, page_size=PAGE,
                      prefill_chunk=CHUNK, seed=11)
    try:
        rng = np.random.default_rng(0)
        futs = [pair.submit(rng.integers(1, VOCAB, n, dtype=np.int32), new)
                for n, new in BACKLOG]
        tokens = [np.asarray(f.result(timeout=600)) for f in futs]
    finally:
        pair.stop()
    for w, g in zip(plain.tokens, tokens):
        np.testing.assert_array_equal(w, g)
    m = pair.prefill.metrics()
    # every request that decodes was handed off with its first token on
    # the host and no launch naming its pages
    # (one fence a launch, whose chunk may finish two prompts)
    assert 1 <= m["fences"]["handoff"] <= pair.handoffs == sum(
        1 for _n, new in BACKLOG if new > 1)
    _accounted(m)
    _accounted(pair.decode.metrics())
    pair.prefill._check_invariants()
    pair.decode._check_invariants()


# ---------------------------------------------------------------------------
# (d) the spans keep what the benchmark's readers need


def _tree(spans):
    """[(name, attrs, [names of the spans open around it])] in time order,
    by containment on the loop's thread."""
    out, open_ = [], []
    for name, t0, dur, tid, attrs in sorted(spans,
                                            key=lambda e: (e[1], -e[2])):
        open_ = [(n, end) for n, end in open_ if end > t0]
        out.append((name, attrs or {}, [n for n, _ in open_]))
        open_.append((name, t0 + dur))
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_the_ticks_keep_their_spans_and_keys(backlog, family):
    ahead, _ = backlog(family, "greedy")
    spans = [e for e in ahead.spans if e[0] != "request"]
    assert tickspans.has_phases(spans)
    tree = _tree(spans)
    tick = {"prefill_tick", "decode_tick"}
    launches = [a for n, a, _ in tree if n == "launch_dispatch"]
    assert len(launches) == ahead.metrics["launches_dispatched"]
    for a in launches:
        assert a["launches"] == 1 and a["ahead"] in (0, 1)
        assert {"rows", "padded_rows", "kv_pages", "qk_pairs",
                "weight_bytes", "pools_passed", "pools_in_place"} <= set(a)
    assert sum(a["ahead"] for a in launches) == \
        ahead.metrics["launches_ahead"]
    # one launch, one upload span: its transfers counted where they are
    # made (1 a chain launch, 3 at a shape's first: its cached chain pair)
    uploads = [a for n, a, _ in tree if n == "launch_h2d"]
    assert len(uploads) == len(launches)
    assert all(a["launches"] == 1 and a["uploads"] in (1, 3)
               for a in uploads)
    assert sum(a["uploads"] for a in uploads) == \
        ahead.metrics["launch_uploads"]
    assert 1.0 <= span_counter.read(
        types.SimpleNamespace(spans=spans), "launch_h2d", "uploads",
        over="launches") <= 3.0
    for name, attrs, around in tree:
        if name in ("launch_build", "launch_h2d", "launch_dispatch",
                    "sample"):
            assert tick & set(around), (name, around)
        if name == "fetch":
            # a tick's fetch; or the idle fence's, inside tick_prep
            assert (tick | {"tick_prep"}) & set(around), around
            assert attrs["bytes"] > 0
        if name == "commit" and "rids" in attrs:
            assert {"finished", "late_stop_rows"} <= set(attrs)
        if name == "prefill_tick":
            assert {"rids", "takes", "decode_waiting", "decode_rode",
                    "chunk_tokens", "padded_rows"} <= set(attrs)
        if name == "decode_tick":
            assert {"rids", "live", "pages_in_use"} <= set(attrs)
    # a tick's fetch and deliver commit belong to the launch BEFORE: the
    # requests a decode tick picked rows of gain their token in the NEXT
    # deliver commit, and every token is named exactly once
    picked, gained = [], []
    for name, attrs, around in tree:
        if name == "decode_tick":
            picked.append(attrs["rids"])
        if name == "commit" and "rids" in attrs:
            gained.append(attrs["rids"])
            if "decode_tick" in around and len(picked) >= 2:
                assert set(picked[-2]) <= set(attrs["rids"])
    flat = [r for rids in gained for r in rids]
    seqs = sorted({r for rids in gained for r in rids})
    assert len(seqs) == len(BACKLOG)
    assert sorted(flat.count(s) for s in seqs) == sorted(
        new for _n, new in BACKLOG)
    its = tickspans.iterations(spans)
    assert sum(it["fetch_ns"] > 0 for it in its) >= len(picked) - 1
    assert token_gap.read(types.SimpleNamespace(spans=spans), 99) > 0.0


@pytest.mark.parametrize("name,moves,cells", [
    ("launch_ahead_share.prefill", "serve_tok_s",
     ["mistral-7b-serve1.longdoc-backlog",
      "mistral-small-4-serve1.longctx-backlog",
      "mellum2-12b-serve1.repo-mixed-backlog"]),
    ("launch_ahead_share.decode", "tpot_p90",
     ["mistral-7b-serve1.chat-steady"]),
])
def test_launch_ahead_share_reads_the_counter_and_is_left_out_at_the_parent(
        backlog, name, moves, cells):
    ahead, drained = backlog("mistral-7b", "greedy")
    with open(os.path.join(REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    reader = dict(metric["reader"])
    assert reader.pop("name") == "span_counter"
    m = ahead.metrics
    assert span_counter.read(types.SimpleNamespace(spans=ahead.spans),
                             **reader) == pytest.approx(
        100.0 * m["launches_ahead"] / m["launches_dispatched"])
    # a program whose spans lack the keys (the parent): nothing to read
    bare = [(n, t, d, tid, {k: v for k, v in (a or {}).items()
                            if k not in ("ahead", "launches")})
            for n, t, d, tid, a in ahead.spans]
    assert span_counter.read(types.SimpleNamespace(spans=bare),
                             **reader) is None
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    # the cells it was added with come first; later cells may join it
    # (PR 44's did) by appending their names
    assert entry and entry[0].pop("workloads")[:len(cells)] == cells
    assert entry == [{"name": name, "unit": "%", "better": "higher",
                      "source": "program_span", "layer": "serving loop",
                      "moves": moves}]
    assert {k: metric[k] for k in ("unit", "layer", "moves", "source")} == {
        k: entry[0][k] for k in ("unit", "layer", "moves", "source")}
