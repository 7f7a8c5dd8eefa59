"""fftrace observability slice: metrics registry, span recorder,
Chrome-trace export, tick ledger, and predicted-vs-measured calibration
(obs/ + tools/fftrace.py)."""

import gzip
import json
import threading
import time
import tracemalloc

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, obs
from flexflow_tpu.obs.calibrate import (
    calibration_report,
    predict_tick_seconds,
    stamp_ledger_meta,
    tick_tokens,
)
from flexflow_tpu.obs.ledger import TickLedger, parse_shape_key, shape_key
from flexflow_tpu.obs.metrics import (
    COUNT_BUCKETS,
    Histogram,
    MetricsRegistry,
    flatten_scalars,
)


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Span recording is process-global: never leak it across tests."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# metrics: histogram bucket math + Prometheus text
# ---------------------------------------------------------------------------


def test_histogram_bucket_math():
    h = Histogram([0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    # per-bucket counts: le 0.1 -> 1, le 1.0 -> 2, le 10.0 -> 1, +Inf -> 1
    assert h.counts == [1, 2, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(56.05)
    d = h.to_json()
    assert d["count"] == 5
    assert 0.1 <= d["p50"] <= 1.0          # 3rd of 5 samples sits in (0.1, 1]
    assert d["p95"] >= 10.0                # tail clamps at/past the last bound
    # boundary values land in the bucket whose le bound they equal
    h2 = Histogram([1.0, 2.0])
    h2.observe(1.0)
    assert h2.counts == [1, 0, 0]


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        Histogram([1.0, 0.5])
    with pytest.raises(ValueError):
        Histogram([])


def test_flatten_scalars_nested():
    flat = flatten_scalars(
        {"a": 1, "b": {"c": 2.5, "d": True, "skip": [1, 2], "n": None}},
        "g")
    assert flat == {"g_a": 1.0, "g_b_c": 2.5, "g_b_d": 1.0}


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("requests_total").inc(3)
    reg.gauge("live_slots").set(2)
    h = reg.histogram("tick_latency_s")
    h.observe(0.002)
    h.observe(0.2)
    text = reg.prometheus_text(extra_scalars={"decode_steps": 7.0,
                                              "pool_pages_free": 5.0})
    assert "# TYPE ff_requests_total counter" in text
    assert "ff_requests_total 3" in text
    assert "# TYPE ff_live_slots gauge" in text
    assert "# TYPE ff_tick_latency_s histogram" in text
    assert 'ff_tick_latency_s_bucket{le="+Inf"} 2' in text
    assert "ff_tick_latency_s_count 2" in text
    assert "ff_tick_latency_s_sum" in text
    # extra scalars: *_steps renders as a counter, the rest as gauges
    assert "# TYPE ff_decode_steps counter" in text
    assert "# TYPE ff_pool_pages_free gauge" in text
    # buckets are cumulative and non-decreasing
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("ff_tick_latency_s_bucket")]
    assert vals == sorted(vals) and vals[-1] == 2


def test_registry_json_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h", COUNT_BUCKETS).observe(3)
    doc = json.loads(json.dumps(reg.to_json()))
    assert doc["c"] == 1
    assert doc["h"]["count"] == 1


# ---------------------------------------------------------------------------
# spans: nesting, threading, Chrome-trace export, disabled-mode overhead
# ---------------------------------------------------------------------------


def test_span_nesting_and_threads(tmp_path):
    rec = obs.enable()
    with obs.span("tick") as sp:
        assert sp
        sp.set(live=2)
        with obs.span("inner"):
            pass

    def other():
        with obs.span("worker") as w:
            w.set(idx=1)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    obs.disable()

    names = [e[0] for e in rec.events]
    assert names == ["inner", "tick", "worker"]  # inner closes first
    tids = {e[0]: e[3] for e in rec.events}
    assert tids["tick"] == tids["inner"] != tids["worker"]
    # nesting: inner's interval lies within tick's
    by = {e[0]: e for e in rec.events}
    assert by["tick"][1] <= by["inner"][1]
    assert (by["inner"][1] + by["inner"][2]
            <= by["tick"][1] + by["tick"][2])

    doc = rec.chrome_trace()
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "M"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(xs[0])
    assert xs[0]["ts"] >= 0.0
    # two threads -> two named tid rows in the tick-loop process
    assert sum(1 for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"
               and e["pid"] == 1) == 2

    # gz export is valid gzipped JSON with the same events
    p = rec.export_chrome_trace(str(tmp_path / "t.json.gz"))
    with gzip.open(p, "rt") as f:
        doc2 = json.load(f)
    assert len(doc2["traceEvents"]) == len(evs)


def test_request_lifecycle_tracks():
    rec = obs.enable()
    t = 1000.0
    rec.record_request(t, t + 0.5, t + 0.7, t + 1.2, label="req 1",
                       attrs={"generated_tokens": 5})
    rec.record_request(t, None, None, t + 0.1, label="req 2", attrs={})
    obs.disable()
    doc = rec.chrome_trace()
    reqs = [e for e in doc["traceEvents"]
            if e["ph"] == "X" and e["pid"] == 2]
    names = {e["name"] for e in reqs}
    # admitted request gets queued/prefill/decode phases; the never-
    # admitted one collapses to a single queued span
    assert {"queued", "prefill", "decode"} <= names
    r1 = [e for e in reqs if e["tid"] == 1]
    assert sum(e["dur"] for e in r1) == pytest.approx(1.2e6, rel=1e-3)


def test_disabled_mode_is_free():
    assert not obs.enabled()
    # identity: every disabled span() call returns the shared singleton
    sp = obs.span("decode_tick")
    assert sp is obs.span("other") is obs.NULL_SPAN
    assert not sp
    with sp as inner:
        assert inner is obs.NULL_SPAN

    # allocation guard: the disabled tick-path pattern must not allocate
    # per call inside the obs package (the null span is pre-built).
    # A handful of one-off interpreter-cache allocations are tolerated;
    # anything O(iterations) fails.
    obs_dir = obs.__file__.rsplit("/", 1)[0]
    iters = 2000

    def tick():
        with obs.span("decode_tick") as s:
            if s:
                s.set(live=3)

    for _ in range(16):
        tick()  # warm any lazy setup
    tracemalloc.start()
    s1 = tracemalloc.take_snapshot()
    for _ in range(iters):
        tick()
    s2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    new_allocs = sum(
        d.count_diff for d in s2.compare_to(s1, "filename")
        if d.traceback[0].filename.startswith(obs_dir) and d.count_diff > 0)
    assert new_allocs < iters // 100


def test_recorder_drops_beyond_max_events():
    rec = obs.enable(max_events=4)
    for i in range(10):
        with obs.span("e"):
            pass
    obs.disable()
    assert len(rec.events) == 4
    assert rec.dropped == 6


# ---------------------------------------------------------------------------
# tick ledger + calibration
# ---------------------------------------------------------------------------


def test_shape_key_roundtrip():
    k = shape_key("verify", batch=3, chunk=0, width=7)
    assert k == "verify|b3|c0|w7"
    assert parse_shape_key(k) == {"phase": "verify", "batch": 3,
                                  "chunk": 0, "width": 7}


def test_ledger_stats_bounding_and_roundtrip(tmp_path):
    led = TickLedger(max_samples_per_shape=8)
    for i in range(20):
        led.record("decode", 0.01 * (i + 1), batch=2)
    led.record("prefill", 0.5, batch=1, chunk=32)
    st = led.stats("decode|b2|c0|w1")
    assert st["count"] == 20          # true event count survives...
    assert st["sampled"] == 8         # ...but only the window is kept
    assert st["min_s"] == pytest.approx(0.13)  # oldest samples evicted
    assert st["max_s"] == pytest.approx(0.20)
    led.meta["note"] = "x"
    led2 = TickLedger.from_json(json.loads(json.dumps(led.to_json())))
    assert led2.shapes() == led.shapes()
    assert led2.stats("decode|b2|c0|w1") == st
    assert led2.meta["note"] == "x"
    p = led.save(str(tmp_path / "led.json"))
    assert TickLedger.load(p).stats("prefill|b1|c32|w1")["count"] == 1


def test_tick_tokens_and_prediction():
    assert tick_tokens("decode", 4, 0, 1) == 4
    assert tick_tokens("verify", 4, 0, 7) == 28
    assert tick_tokens("prefill", 4, 32, 1) == 32
    # base step prices 100 tokens in 1s -> a 4-row decode tick is 40ms
    assert predict_tick_seconds(1.0, 100, "decode", 4) == pytest.approx(0.04)


def test_calibration_report_math():
    led = TickLedger()
    for _ in range(5):
        led.record("decode", 0.04, batch=2)     # predicted 0.02 -> ratio 2
        led.record("verify", 0.07, batch=1, width=7)  # pred 0.07 -> ratio 1
    predicted = {"predicted_step_s": 1.0, "graph_tokens": 100,
                 "pricing_mode": "test"}
    rep = calibration_report(led, predicted=predicted)
    assert rep["base"]["pricing_mode"] == "test"
    dk = shape_key("decode", 2)
    assert rep["shapes"][dk]["predicted_s"] == pytest.approx(0.02)
    assert rep["shapes"][dk]["ratio"] == pytest.approx(2.0)
    assert rep["tick_scales"][dk] == pytest.approx(2.0)
    assert rep["phases"]["decode"] == pytest.approx(2.0)
    assert rep["phases"]["verify"] == pytest.approx(1.0)

    # an unstamped ledger refuses to calibrate
    with pytest.raises(ValueError, match="predicted_step_s"):
        calibration_report(TickLedger())


def test_measured_cost_model_consumes_tick_scales():
    from flexflow_tpu.search.machine_model import TPUMachineModel
    from flexflow_tpu.search.measured import MeasuredCostModel

    m = MeasuredCostModel(TPUMachineModel.make("v5e", 8), {"data": 8})
    assert m.tick_scale("decode", 2) == 1.0  # uncalibrated -> identity
    n = m.set_tick_calibration({
        "tick_scales": {shape_key("decode", 2): 2.5,
                        shape_key("verify", 2, width=7): 4.0},
        "phases": {"decode": 3.0},
    })
    assert n == 2  # exact shapes (phase fallbacks stored separately)
    assert m.tick_scale("decode", 2) == pytest.approx(2.5)       # exact
    assert m.tick_scale("decode", 16) == pytest.approx(3.0)      # phase med.
    assert m.tick_scale("prefill", 1, chunk=8) == 1.0            # unknown
    # a bare {key: ratio} dict (tick_scales alone) is accepted too
    m2 = MeasuredCostModel(TPUMachineModel.make("v5e", 8), {"data": 8})
    m2.set_tick_calibration({shape_key("decode", 4): 1.5})
    assert m2.tick_scale("decode", 4) == pytest.approx(1.5)
    with pytest.raises(TypeError):
        m2.set_tick_calibration([1, 2])


# ---------------------------------------------------------------------------
# end to end: traced paged+speculative serving -> trace + calibration
# ---------------------------------------------------------------------------


def _causal_lm():
    from flexflow_tpu import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    lcfg = LlamaConfig.tiny()
    ff = FFModel(FFConfig(batch_size=1, seed=7))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


def test_traced_serving_end_to_end(tmp_path):
    """A paged + speculative serving run under obs.enable() yields a
    Perfetto-loadable trace with nested tick-phase spans and per-request
    lifecycle tracks, a populated tick ledger, and a calibration report
    whose scales MeasuredCostModel accepts (ISSUE 8 acceptance)."""
    from flexflow_tpu.spec import SpecConfig

    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 6, 4)]
    rec = obs.enable()
    try:
        for speculate in (None, SpecConfig(width=2, depth=3)):
            server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                         page_size=8, speculate=speculate)
            try:
                futs = [server.submit(p, max_new_tokens=4) for p in prompts]
                for f in futs:
                    f.result(timeout=300)
            finally:
                server.stop()
    finally:
        obs.disable()

    names = {e[0] for e in rec.events}
    assert {"tick_prep", "admit_pending", "prefill_tick", "decode_tick",
            "draft", "verify", "commit"} <= names
    assert len(rec.requests) == 2 * len(prompts)

    # decode AND verify tick shapes landed in the ledger
    phases = {parse_shape_key(k)["phase"] for k in rec.ledger.shapes()}
    assert {"decode", "verify"} <= phases

    # stamped ledger -> saved artifact -> calibration report, offline
    stamp_ledger_meta(rec.ledger, ff, fixture="test")
    path = rec.ledger.save(str(tmp_path / "ledger.json"))
    rep = calibration_report(TickLedger.load(path))
    assert rep["base"]["predicted_step_s"] > 0
    assert set(rep["phases"]) >= {"decode", "verify"}
    assert all(r > 0 for r in rep["tick_scales"].values())

    trace = rec.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(trace))
    assert any(e["ph"] == "X" and e["pid"] == 2 and e["name"] == "decode"
               for e in doc["traceEvents"])


def test_fftrace_calibrate_cli(tmp_path, capsys):
    import tools.fftrace as fft

    led = TickLedger()
    led.record("decode", 0.03, batch=2)
    led.meta.update({"predicted_step_s": 1.0, "graph_tokens": 100})
    p = str(tmp_path / "led.json")
    led.save(p)
    out = str(tmp_path / "rep.json")
    assert fft.main(["calibrate", p, "--out", out]) == 0
    rep = json.load(open(out))
    assert rep["tick_scales"][shape_key("decode", 2)] == pytest.approx(1.5)
    # unstamped ledger -> clean CLI error, not a traceback
    p2 = str(tmp_path / "bare.json")
    TickLedger().save(p2)
    assert fft.main(["calibrate", p2]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# request log (obs.reqlog): bounded retention, null discipline, JSONL
# ---------------------------------------------------------------------------


def test_bounded_ring_retention_and_drop_count():
    ring = obs.BoundedRing(3)
    assert ring.capacity == 3
    for i in range(5):
        ring.append(i)
    assert ring.snapshot() == [2, 3, 4]    # keep-newest
    assert ring.dropped == 2               # ...and COUNT what fell off
    assert len(ring) == 3
    assert ring.tail(2) == [3, 4]
    assert ring.tail(0) == []
    assert ring.tail(99) == [2, 3, 4]
    assert list(ring) == [2, 3, 4]
    with pytest.raises(ValueError):
        obs.BoundedRing(0)


def test_request_log_factory_null_discipline():
    # None -> live log at the default capacity; 0 -> the shared falsy
    # singleton; N -> live log at N (same contract as obs.span)
    live = obs.request_log(None)
    assert live and live.capacity == 4096
    assert obs.request_log(7).capacity == 7
    null = obs.request_log(0)
    assert null is obs.NULL_REQLOG and not null
    null.log({"x": 1})                     # no-op, never raises
    assert len(null) == 0 and null.records() == [] and null.tail(5) == []
    assert null.dropped == 0 and null.capacity == 0

    log = obs.RequestLog(capacity=2)
    for i in range(3):
        log.log({"rid": i})
    assert [r["rid"] for r in log.records()] == [1, 2]
    assert log.dropped == 1


def test_disabled_reqlog_is_free():
    """The disabled emit-site pattern (`if rl: rl.log(...)`) must not
    allocate per call inside the obs package — same guard as the null
    span."""
    rl = obs.request_log(0)
    obs_dir = obs.__file__.rsplit("/", 1)[0]
    iters = 2000

    def emit():
        if rl:
            rl.log({"rid": 1})

    for _ in range(16):
        emit()
    tracemalloc.start()
    s1 = tracemalloc.take_snapshot()
    for _ in range(iters):
        emit()
    s2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    new_allocs = sum(
        d.count_diff for d in s2.compare_to(s1, "filename")
        if d.traceback[0].filename.startswith(obs_dir) and d.count_diff > 0)
    assert new_allocs < iters // 100


def test_reqlog_jsonl_roundtrip(tmp_path):
    from flexflow_tpu.obs import reqlog as reqlog_mod

    records = [{"submit_ns": 10 * i, "rid": i, "prompt_tokens": 4,
                "prefix_chain": ["aa", "bb"]} for i in range(3)]
    for name in ("log.jsonl", "log.jsonl.gz"):
        p = str(tmp_path / name)
        assert reqlog_mod.dump_jsonl(p, records) == 3
        assert reqlog_mod.load_jsonl(p) == records
    # the plain export leads with the schema header line
    first = open(str(tmp_path / "log.jsonl")).readline()
    assert json.loads(first) == {"schema": reqlog_mod.SCHEMA}
    # headerless hand-built fixtures load too...
    bare = str(tmp_path / "bare.jsonl")
    with open(bare, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    assert reqlog_mod.load_jsonl(bare) == records
    # ...but a FOREIGN schema is refused by name, not priced as garbage
    alien = str(tmp_path / "alien.jsonl")
    with open(alien, "w") as f:
        f.write(json.dumps({"schema": "somebody.else/v9"}) + "\n")
    with pytest.raises(ValueError, match="somebody.else/v9"):
        reqlog_mod.load_jsonl(alien)


# ---------------------------------------------------------------------------
# SLO monitor (obs.slo): percentile math, latching, breach dumps
# ---------------------------------------------------------------------------


def _slo_rec(i, ttft_s, decode_s=0.0, decode_tokens=1):
    sub = i * 10**9
    first = sub + int(ttft_s * 1e9)
    return {"submit_ns": sub, "first_token_ns": first,
            "done_ns": first + int(decode_s * 1e9),
            "decode_tokens": decode_tokens}


def test_slo_percentile_nearest_rank():
    from flexflow_tpu.obs.slo import percentile

    assert percentile([], 0.95) == 0.0
    assert percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert percentile(list(range(1, 11)), 0.95) == 10  # ceil(9.5) = 10th
    assert percentile([1.0, 2.0, 3.0], 0.95) == 3.0    # ceil(2.85) = 3rd
    assert percentile([5.0], 0.95) == 5.0


def test_slo_target_validation_and_roundtrip():
    with pytest.raises(ValueError, match="declares no target"):
        obs.SLOTarget()
    with pytest.raises(ValueError):
        obs.SLOTarget(ttft_p95_s=0.1, window=0)
    t = obs.SLOTarget(ttft_p95_s=0.1, s_per_token_p95=0.02, window=16,
                      min_samples=4)
    assert obs.SLOTarget.from_json(
        json.loads(json.dumps(t.to_json()))) == t


def test_slo_monitor_latches_per_excursion():
    """Breach is an EVENT, not a state poll: observe() returns True
    exactly on the ok -> breached transition (counted once per
    excursion), stays latched while the window p95 is over, and
    unlatches on recovery so the NEXT excursion counts again."""
    mon = obs.SLOMonitor(obs.SLOTarget(ttft_p95_s=0.1, window=4,
                                       min_samples=2))
    i = iter(range(100))
    assert mon.observe(_slo_rec(next(i), 0.01)) is False  # < min_samples
    assert mon.observe(_slo_rec(next(i), 0.01)) is False  # p95 .01 ok
    assert mon.observe(_slo_rec(next(i), 1.0)) is True    # trip: p95 1.0
    assert mon.breaches == 1 and mon.breached
    assert mon.observe(_slo_rec(next(i), 1.0)) is False   # still breached
    assert mon.breaches == 1
    for _ in range(4):                                    # flush the window
        mon.observe(_slo_rec(next(i), 0.01))
    assert not mon.breached                               # recovered
    assert mon.observe(_slo_rec(next(i), 2.0)) is True    # new excursion
    assert mon.breaches == 2
    # goodput = per-request pass fraction over the window (3 fast + the
    # 2.0s straggler in the last 4)
    assert mon.goodput == pytest.approx(3 / 4)
    snap = mon.snapshot()
    assert snap["breaches"] == 2 and snap["breached"]
    assert snap["ttft_p95_s"] == pytest.approx(2.0)       # nearest-rank


def test_slo_monitor_s_per_token_axis():
    mon = obs.SLOMonitor(obs.SLOTarget(s_per_token_p95=0.01, window=8,
                                       min_samples=1))
    # 0.4 s of decode for 80 tokens = 5 ms/token: ok
    assert mon.observe(_slo_rec(0, 0.0, decode_s=0.4,
                                decode_tokens=80)) is False
    # 0.4 s for 10 tokens = 40 ms/token: trips
    assert mon.observe(_slo_rec(1, 0.0, decode_s=0.4,
                                decode_tokens=10)) is True


def test_slo_breach_dump_bundle(tmp_path):
    """A breach dump is the complete flight-recorder bundle: reqlog
    tail, Chrome-trace tail, metrics snapshot, SLO snapshot — and a
    FAILING metrics callable is captured as an error entry, never
    raised into the serving loop."""
    from flexflow_tpu.obs import reqlog as reqlog_mod

    mon = obs.SLOMonitor(obs.SLOTarget(ttft_p95_s=0.1, min_samples=1),
                         dump_dir=str(tmp_path / "dumps"))
    log = obs.RequestLog(capacity=8)
    for i in range(5):
        rec = _slo_rec(i, 1.0 if i == 4 else 0.01)
        log.log(rec)
        mon.observe(rec)
    assert mon.breaches == 1
    recorder = obs.enable()
    with obs.span("decode_tick"):
        pass
    bundle = mon.dump(reqlog=log, recorder=recorder,
                      metrics=lambda: {"requests_served": 5})
    obs.disable()
    assert bundle == str(tmp_path / "dumps" / "breach_0001")
    tail = reqlog_mod.load_jsonl(bundle + "/reqlog_tail.jsonl")
    assert len(tail) == 5 and tail[-1]["first_token_ns"] > 0
    trace = json.load(open(bundle + "/trace_tail.json"))
    assert any(e["ph"] == "X" and e["name"] == "decode_tick"
               for e in trace["traceEvents"])
    assert json.load(open(bundle + "/metrics.json")) == {
        "requests_served": 5}
    slo_doc = json.load(open(bundle + "/slo.json"))
    assert slo_doc["breaches"] == 1 and slo_doc["breached"]
    assert mon.last_dump == bundle

    # a metrics() that explodes becomes an error entry in the bundle
    def boom():
        raise RuntimeError("scrape died")

    mon.breaches += 1
    b2 = mon.dump(reqlog=log, metrics=boom)
    assert "scrape died" in json.load(open(b2 + "/metrics.json"))["error"]
    # no dump_dir -> no bundle, no error
    assert obs.SLOMonitor(obs.SLOTarget(ttft_p95_s=1.0)).dump() is None


# ---------------------------------------------------------------------------
# end to end: record a mixed paged+spec run, replay it deterministically
# ---------------------------------------------------------------------------


def _serve_recorded(ff, lcfg, prompts, speculate=None, max_new=4,
                    max_len=32, **kw):
    srv = ff.serve_generation(slots=2, max_len=max_len, paged=True,
                              page_size=4, speculate=speculate, **kw)
    try:
        futs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        for f in futs:
            f.result(timeout=300)
        return srv.request_log.records(), srv.metrics()
    finally:
        srv.stop()


def test_reqlog_record_and_deterministic_replay(tmp_path):
    """ISSUE 15 acceptance: record a tiny mixed paged+spec run, export,
    re-import, re-serve the same prompts — request count, per-request
    token counts, and the content-hash prefix chains agree EXACTLY
    (greedy serving is deterministic, and the chains hash page content,
    so equality here proves the replay re-served the same pages). The
    token-cyclic fixture makes the drafter productive, so the records
    carry REAL accepted/proposed counts for the pricer to measure."""
    from flexflow_tpu.obs import reqlog as reqlog_mod
    from flexflow_tpu.spec import SpecConfig
    from flexflow_tpu.spec.fixtures import make_token_cyclic

    ff, lcfg = _causal_lm()
    make_token_cyclic(ff)
    rs = np.random.RandomState(9)
    shared = rs.randint(0, lcfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rs.randint(0, lcfg.vocab_size, (n,))
                               .astype(np.int32)]) for n in (1, 4, 2)]

    plain, m = _serve_recorded(ff, lcfg, prompts)
    assert len(plain) == len(prompts)
    assert m["reqlog"] == {"enabled": True, "records": len(prompts),
                           "capacity": 4096, "dropped": 0}
    # spec pass: a 40-token budget lets the cyclic stream repeat, so
    # the n-gram drafter actually drafts and the records carry real
    # proposed/accepted counts
    spec, _ = _serve_recorded(ff, lcfg, prompts,
                              SpecConfig(width=2, depth=3),
                              max_new=40, max_len=64)
    records = plain + spec

    # schema: every record carries the full flight-recorder field set
    for r in records:
        assert (r["submit_ns"] <= r["admit_ns"] <= r["first_token_ns"]
                <= r["done_ns"])
        assert r["kv_dtype"] == "float32" and r["page_size"] == 4
        assert r["decode_tokens"] == r["max_new_tokens"]
        assert r["prompt_tokens"] in (5, 8, 6)
        assert len(r["prefix_chain"]) == r["prompt_tokens"] // 4
        assert r["phases"]["queue_s"] >= 0.0
        assert r["temperature"] == 0.0 and r["preemptions"] == 0
    # the speculative pass recorded real drafting; the plain pass none
    assert sum(r["spec_draft_tokens"] for r in plain) == 0
    assert sum(r["spec_draft_tokens"] for r in spec) > 0
    assert sum(r["spec_accepted_tokens"] for r in spec) > 0
    # all six prompts open with the same 4-token (one-page) prefix:
    # the sha1 chains must agree on their first entry across ALL records
    assert len({r["prefix_chain"][0] for r in records}) == 1

    # export -> import is lossless (the replay substrate)
    p = str(tmp_path / "run.jsonl")
    assert reqlog_mod.dump_jsonl(p, records) == 6
    assert reqlog_mod.load_jsonl(p) == records

    # deterministic replay: a fresh identical server over the same
    # prompts produces records that agree exactly on everything
    # content-derived (counts + hash chains; wall-clock stamps differ,
    # and the cached-vs-computed prefill split is admission-timing
    # dependent — only its SUM is content-derived)
    replay, _ = _serve_recorded(ff, lcfg, prompts)
    keys = ("prompt_tokens", "decode_tokens", "prefix_chain")
    assert ([{k: r[k] for k in keys} for r in replay]
            == [{k: r[k] for k in keys} for r in plain])
    for r in replay + records:
        assert (r["prefill_tokens"] + r["cached_prefill_tokens"]
                == r["prompt_tokens"])


def test_reqlog_disabled_and_bounded_on_server():
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(10)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 4)]
    # capacity 0 disables: the server holds the falsy NULL_REQLOG
    recs, m = _serve_recorded(ff, lcfg, prompts, reqlog_capacity=0)
    assert recs == [] and m["reqlog"]["enabled"] is False
    # capacity 2 keeps the newest 2 and counts the drop in /v2 metrics
    recs, m = _serve_recorded(ff, lcfg, prompts, reqlog_capacity=2)
    assert len(recs) == 2
    assert m["reqlog"] == {"enabled": True, "records": 2, "capacity": 2,
                           "dropped": 1}


def test_slo_breach_capture_end_to_end(tmp_path):
    """A served run with an unmeetable declared SLO trips the monitor:
    ff_slo_breaches_total counts the excursion, goodput drops, the
    metrics payload carries the SLO snapshot, and the dump bundle lands
    complete (reqlog tail + trace tail + metrics + slo) — captured from
    INSIDE the serving loop, proving breach capture never deadlocks the
    loop that triggers it."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 5, 4)]
    dump_dir = str(tmp_path / "dumps")
    rec = obs.enable()
    try:
        recs, m = _serve_recorded(
            ff, lcfg, prompts,
            slo={"ttft_p95_s": 1e-9, "window": 8, "min_samples": 1},
            slo_dump_dir=dump_dir)
    finally:
        obs.disable()
    assert rec.events  # the trace tail had spans to capture
    slo = m["slo"]
    assert slo["breaches"] == 1 and slo["breached"]
    assert slo["goodput_ratio"] == 0.0       # nobody met 1 ns TTFT
    assert slo["target"]["ttft_p95_s"] == 1e-9
    bundle = slo["last_dump"]
    assert bundle == dump_dir + "/breach_0001"
    for name in ("reqlog_tail.jsonl", "trace_tail.json", "metrics.json",
                 "slo.json", "strategy.json", "compile.json"):
        assert (tmp_path / "dumps" / "breach_0001" / name).exists(), name
    # the dump ran mid-loop: its metrics snapshot already carries the
    # tripping request's reqlog record and the breach count
    dumped = json.load(open(bundle + "/metrics.json"))
    assert dumped["reqlog"]["records"] >= 1
    assert dumped["slo"]["breaches"] == 1
    # the bundle says WHAT was breaching: the active ServeStrategy and
    # whether recompiles were part of the excursion (ISSUE 16 satellite)
    strat = json.load(open(bundle + "/strategy.json"))
    assert strat["page_size"] == 4
    comp = json.load(open(bundle + "/compile.json"))
    assert comp["compile_events_total"] >= 1
    assert comp["steady_state_recompiles"] == 0


def test_slo_prometheus_series_gated_on_target():
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(12)
    p = rs.randint(0, lcfg.vocab_size, (4,)).astype(np.int32)
    # with a target: breach counter + goodput gauge in the registry text
    srv = ff.serve_generation(slots=1, max_len=32, paged=True, page_size=4,
                              slo=obs.SLOTarget(ttft_p95_s=1e-9,
                                                min_samples=1))
    try:
        srv.generate(p, max_new_tokens=2)
        text = srv.registry.prometheus_text()
    finally:
        srv.stop()
    assert "# TYPE ff_slo_breaches_total counter" in text
    assert "ff_slo_breaches_total 1" in text
    assert "# TYPE ff_goodput_ratio gauge" in text
    assert "ff_goodput_ratio 0" in text
    # without one: no dead series
    srv = ff.serve_generation(slots=1, max_len=32)
    try:
        text = srv.registry.prometheus_text()
    finally:
        srv.stop()
    assert "slo_breaches" not in text and "goodput" not in text


def test_fftrace_replay_cli(tmp_path, capsys):
    """`fftrace replay log.jsonl` re-serves a recorded log and reports
    recorded-vs-replayed TTFT/throughput deltas (ISSUE 15 satellite)."""
    import tools.fftrace as fft

    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 6)]
    recs, _ = _serve_recorded(ff, lcfg, prompts)
    log = str(tmp_path / "run.jsonl")
    from flexflow_tpu.obs import reqlog as reqlog_mod

    reqlog_mod.dump_jsonl(log, recs)
    assert fft.main(["replay", log, "--out", str(tmp_path)]) == 0
    capsys.readouterr()
    rep = json.load(open(str(tmp_path / "replay_report.json")))
    assert rep["profile"] == f"replay:{log.rsplit('/', 1)[-1]}"
    assert rep["speculate"] is False          # the log never drafted
    assert rep["recorded"]["requests"] == 2
    assert rep["replayed"]["requests"] == 2
    assert rep["replayed"]["decode_tokens"] == rep["recorded"][
        "decode_tokens"]
    for k in ("ttft_p50_s", "ttft_p95_s", "tokens_per_s"):
        assert k in rep["delta"]
    assert "paced" not in rep                 # opt-in only
    # --pace=SPEEDUP additionally replays the recorded interarrival
    # gaps (compressed 50x so the test stays fast) — the paced section
    # reports its own replayed stats and deltas (ISSUE 16 satellite)
    assert fft.main(["replay", log, "--out", str(tmp_path),
                     "--pace", "50"]) == 0
    capsys.readouterr()
    rep = json.load(open(str(tmp_path / "replay_report.json")))
    paced = rep["paced"]
    assert paced["speedup"] == 50.0
    assert paced["replayed"]["requests"] == 2
    assert paced["replayed"]["decode_tokens"] == rep["recorded"][
        "decode_tokens"]
    for k in ("ttft_p50_s", "ttft_p95_s", "tokens_per_s"):
        assert k in paced["delta"]


# ---------------------------------------------------------------------------
# span links, request ids from submission, the phases inside a tick, the
# clock beacon (ISSUE 24)
# ---------------------------------------------------------------------------


def test_span_links_across_two_threads():
    """Every span has an id of its own and the id of the span open on the
    SAME thread when it started; another thread's open span is no parent."""
    rec = obs.enable()
    inside = threading.Event()
    release = threading.Event()

    def other():
        with obs.span("worker"):
            with obs.span("worker_child"):
                inside.set()
                release.wait(10)

    with obs.span("tick"):
        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with obs.span("inner"):     # opened while `worker` is open elsewhere
            with obs.span("leaf"):
                pass
        release.set()
        t.join(10)
        assert not t.is_alive()
    with obs.span("after"):
        pass
    obs.disable()
    by = {e[0]: e[4] for e in rec.events}
    ids = [a["id"] for a in by.values()]
    assert len(set(ids)) == len(ids) == 6
    assert by["tick"]["parent"] is None and by["worker"]["parent"] is None
    assert by["inner"]["parent"] == by["tick"]["id"]
    assert by["leaf"]["parent"] == by["inner"]["id"]
    assert by["worker_child"]["parent"] == by["worker"]["id"]
    assert by["after"]["parent"] is None     # the stack unwound
    # set() adds to the links, it does not replace them
    rec = obs.enable()
    with obs.span("a") as sp:
        sp.set(live=2)
    assert set(rec.events[0][4]) == {"id", "parent", "live"}


def test_recorder_is_a_ring_that_keeps_the_newest():
    """Once full, the recorder pushes out the OLDEST event: a loop that
    idled through a long warm-up still has the spans of its traffic."""
    rec = obs.enable(max_events=5)
    for i in range(8):
        with obs.span("idle") as sp:
            sp.set(i=i)
    rec.instant("mark", n=1)
    with obs.span("traffic"):
        pass
    obs.disable()
    assert len(rec.events) == 5 and rec.dropped == 5
    assert [e[0] for e in rec.events] == ["idle"] * 3 + ["mark", "traffic"]
    assert [e[4]["i"] for e in rec.events if e[0] == "idle"] == [5, 6, 7]
    assert rec.chrome_trace()["otherData"]["dropped_events"] == 5


def test_a_span_that_closes_late_does_not_break_a_reader():
    """A span open on another thread when `disable()` returned (another
    server's idling loop) closes into the ring while a reader walks
    `rec.events`: the reader sees the events it started with."""
    rec = obs.enable()
    late = obs.span("idle_wait").__enter__()
    with obs.span("traffic"):
        pass
    obs.disable()
    seen = []
    for ev in rec.events:
        late.__exit__(None, None, None)     # closes once, mid-walk
        seen.append(ev[0])
    assert seen == ["traffic"]
    assert [e[0] for e in rec.events][:2] == ["traffic", "idle_wait"]


def test_span_left_open_does_not_adopt_later_spans():
    """A manual __enter__ whose __exit__ an exception skipped must not
    become the parent of every later span on the thread."""
    rec = obs.enable()
    with obs.span("outer"):
        obs.span("lost").__enter__()        # never exited
    with obs.span("next"):
        pass
    by = {e[0]: e[4] for e in rec.events}
    assert by["next"]["parent"] is None


def test_beacon_is_rate_limited_and_named_by_its_stamp():
    names = []

    class Ann:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    rec = obs.enable()
    rec._annotation = Ann
    t_lo = time.monotonic_ns()
    obs.beacon()
    obs.beacon()                    # within BEACON_NS of the first: dropped
    rec._beacon_ns -= 2 * obs.trace.BEACON_NS
    obs.beacon()
    t_hi = time.monotonic_ns()
    obs.disable()
    obs.beacon()                    # disabled: nothing, and no error
    assert len(names) == 2 and all(n.startswith("ffclock:") for n in names)
    stamps = [int(n.split(":", 1)[1]) for n in names]
    assert t_lo <= stamps[0] <= stamps[1] <= t_hi
    inst = [e for e in rec.events if e[0] == "ffclock"]
    assert [e[4]["stamp"] for e in inst] == stamps
    assert all(e[2] == 0 for e in inst)


def _paged_pair(ff, lcfg, rec_annotation=None, **kw):
    """Two scripted requests (prompts of 5 and 11 tokens, 3 new tokens
    each) through a 2-slot paged server whose loop starts only after both
    are queued: page 8, prefill budget 8 a tick, so request 1 finishes its
    prompt in the first prefill tick and request 2 in the second."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11)]
    srv = ff.serve_generation(slots=2, max_len=32, paged=True, page_size=8,
                              prefill_chunk=8, defer_start=True, **kw)
    try:
        futs = [srv.submit(p, max_new_tokens=3) for p in prompts]
        srv.start()
        tokens = [f.result(timeout=300) for f in futs]
        records = srv.request_log.records()
    finally:
        srv.stop()
    return prompts, tokens, records


def test_traced_chunk_pieces_share_one_walk():
    """A 20-token prompt under a 24-token budget rides ONE launch as
    pieces of 8, 8 and 4 rows: three pieces, one walk, its pages counted
    once, on the `launch_dispatch` span and in `metrics()`; the decode
    launches after it share nothing."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(9)
    prompt = rs.randint(0, lcfg.vocab_size, (20,)).astype(np.int32)
    rec = obs.enable()
    srv = ff.serve_generation(slots=2, max_len=32, paged=True, page_size=8,
                              prefill_chunk=24)
    try:
        srv.submit(prompt, max_new_tokens=3).result(timeout=300)
        m = srv.metrics()
    finally:
        srv.stop()
        obs.disable()
    launches = [e[4] for e in sorted(rec.events, key=lambda e: e[1])
                if e[0] == "launch_dispatch"]
    first = launches[0]
    assert (first["kv_pieces"], first["kv_walks"],
            first["kv_pieces_shared"]) == (3, 1, 2)
    assert first["kv_rows"] == 20 and first["kv_pages"] == 3
    assert first["kv_blocks"] == 1
    assert first["qk_pairs"] == 20 * 21 // 2
    for later in launches[1:]:
        assert later["kv_pieces"] == later["kv_walks"] == 1
        assert later["kv_pieces_shared"] == 0
    assert m["kv_pieces"] == sum(a["kv_pieces"] for a in launches)
    assert m["kv_walks"] == sum(a["kv_walks"] for a in launches)
    assert m["kv_pieces_shared"] == 2


def test_disabled_paged_tick_builds_no_span_attrs_or_beacon(monkeypatch):
    """With tracing off a paged server's whole tick path (launch phases,
    sample, fetch, commit, beacon) touches NULL_SPAN only: no Span is
    built, no attribute is set, no beacon is emitted; a request still gets
    its `seq` at submission."""
    ff, lcfg = _causal_lm()
    spans = []
    real_span = obs.span

    def watched(name):
        sp = real_span(name)
        spans.append((name, sp))
        return sp

    def forbidden(*a, **kw):
        raise AssertionError("built while tracing is off")

    monkeypatch.setattr(obs, "span", watched)
    monkeypatch.setattr(obs.trace.Span, "__init__", forbidden)
    monkeypatch.setattr(obs.trace._NullSpan, "set", forbidden)
    monkeypatch.setattr(obs.trace.TraceRecorder, "beacon", forbidden)
    monkeypatch.setattr(obs.trace.TraceRecorder, "instant", forbidden)
    _prompts, tokens, records = _paged_pair(ff, lcfg)
    assert [len(t) for t in tokens] == [3, 3]
    seen = {name for name, _sp in spans}
    assert {"tick_prep", "prefill_tick", "decode_tick", "launch_build",
            "launch_h2d", "launch_dispatch", "sample", "fetch",
            "commit"} <= seen
    assert all(sp is obs.NULL_SPAN for _name, sp in spans)
    assert sorted(r["seq"] for r in records) == [1, 2]


def test_traced_paged_tick_phases(tmp_path):
    """A traced tiny paged server: (a) every phase lies inside its parent
    and siblings never overlap; (b) token times rebuilt from `commit.rids`
    (the deliver commits: a tick takes the tokens of the launch BEFORE)
    give len(tokens) per request, the first within 1 ms of the record's
    `first_token_ns`; (c) `kv_rows`/`kv_pages`/`kv_blocks`/`qk_pairs` of every launch
    equal an independent count from the requests' own progress; (d) the
    beacons parse to stamps inside the run; the request log carries `seq`
    and `fftrace summarize` prints self time from the links."""
    import tools.fftrace as fft
    from flexflow_tpu.paged.scheduler import PREFILL_WINDOW_ROWS

    ff, lcfg = _causal_lm()
    rec = obs.enable()
    t_lo = time.monotonic_ns()
    prompts, tokens, records = _paged_pair(ff, lcfg)
    t_hi = time.monotonic_ns()
    obs.disable()
    assert rec.dropped == 0
    events = [e for e in rec.events if e[2] > 0 or e[0] != "ffclock"]
    by_id = {e[4]["id"]: e for e in events}
    kids = {}
    for e in events:
        if e[4]["parent"] is not None:
            kids.setdefault(e[4]["parent"], []).append(e)

    # (a) containment and no overlap among siblings
    leaves = {"launch_build", "launch_h2d", "launch_dispatch", "sample",
              "fetch", "commit"}
    assert leaves <= {e[0] for e in events}
    for e in events:
        if e[0] in leaves:
            top = e
            while top[4]["parent"] is not None:
                par = by_id[top[4]["parent"]]
                assert par[1] <= top[1] and top[1] + top[2] <= par[1] + par[2]
                top = par
            # a tick; or the fence of an iteration with nothing to launch
            # (the last tokens are taken inside `tick_prep`)
            assert top[0] in ("prefill_tick", "decode_tick") or (
                top[0] == "tick_prep" and e[0] in ("fetch", "commit"))
    for sibs in kids.values():
        sibs.sort(key=lambda e: e[1])
        for a, b in zip(sibs, sibs[1:]):
            assert a[1] + a[2] <= b[1]
    # a finishing prompt's pick is a sample inside the tick's own
    assert any(by_id[e[4]["parent"]][0] == "sample"
               for e in events if e[0] == "sample")

    # (b) token times from commit.rids
    rec_by_seq = {r["seq"]: r for r in records}
    assert sorted(rec_by_seq) == [1, 2]
    times = {1: [], 2: []}
    for e in sorted(events, key=lambda e: e[1] + e[2]):
        if e[0] == "commit":
            for seq in e[4].get("rids", ()):
                times[seq].append(e[1] + e[2])
    for seq, toks in zip((1, 2), tokens):
        assert len(times[seq]) == len(toks) == 3
        assert abs(times[seq][0] - rec_by_seq[seq]["first_token_ns"]) < 1e6
    finished = sum(e[4].get("finished", 0) for e in events
                   if e[0] == "commit")
    assert finished == 2

    # (c) the launch counts against the requests' own progress
    P, W_MAX = 8, PREFILL_WINDOW_ROWS
    plen = {1: len(prompts[0]), 2: len(prompts[1])}
    filled = {1: 0, 2: 0}       # prompt rows in the cache
    made = {1: 0, 2: 0}         # tokens generated
    ticks = sorted((e for e in events
                    if e[0] in ("prefill_tick", "decode_tick")),
                   key=lambda e: e[1])
    assert ticks[0][4]["rids"] == [1, 2] and ticks[0][4]["takes"] == [5, 3]
    rode = 0
    for n, tick in enumerate(ticks):
        launch = [k for k in kids[tick[4]["id"]]
                  if k[0] == "launch_dispatch"]
        commit = [k for k in kids[tick[4]["id"]] if k[0] == "commit"]
        # ONE advance, and at most one deliver (of the launch before's)
        assert sum("rids" not in k[4] for k in commit) == 1
        assert sum("rids" in k[4] for k in commit) <= 1
        if tick[0] == "decode_tick" and ticks[n - 1][4].get("decode_rode"):
            # its rows rode the chunk's launch, counted there (below)
            assert not launch
            for seq in tick[4]["rids"]:
                made[seq] += 1
            continue
        assert len(launch) == 1
        got = launch[0][4]
        # the launch's uploads beside it: the one packed descriptor, and
        # at a shape's first launch its cached chain pair
        h2d = [k[4] for k in kids[tick[4]["id"]] if k[0] == "launch_h2d"]
        assert len(h2d) == 1 and h2d[0]["launches"] == 1
        assert h2d[0]["uploads"] in (1, 3)
        items, seqs = [], []            # (pos, q_len) with work, whose
        if tick[0] == "prefill_tick":
            W = min(W_MAX, max(tick[4]["takes"]))
            for seq, take in zip(tick[4]["rids"], tick[4]["takes"]):
                for off in range(0, take, W):
                    items.append((filled[seq] + off, min(W, take - off)))
                    seqs.append(seq)
                filled[seq] += take
            if tick[4]["decode_rode"]:
                # the decoding slots' q_len 1 items, behind the pieces
                assert ticks[n + 1][0] == "decode_tick"
                items += [(plen[seq] + made[seq] - 1, 1)
                          for seq in ticks[n + 1][4]["rids"]]
                seqs += ticks[n + 1][4]["rids"]
                rode += 1
        else:
            items = [(plen[seq] + made[seq] - 1, 1)
                     for seq in tick[4]["rids"]]
            seqs = list(tick[4]["rids"])
        # a page counts once a WALK: a piece that continues the item
        # before it (one request's chunk) rides that item's walk
        walks = []                              # horizon of each walk
        for i, (p, q) in enumerate(items):
            if i and seqs[i] == seqs[i - 1] and p == sum(items[i - 1]):
                walks[-1] = p + q
            else:
                walks.append(p + q)
        assert got["kv_pieces"] == len(items)
        assert got["kv_walks"] == len(walks)
        assert got["kv_pieces_shared"] == len(items) - len(walks)
        assert got["kv_rows"] == sum(walks)
        assert got["kv_pages"] == sum(-(-e // P) for e in walks)
        # the walk's blocks at the kernel's derived block size: the fill
        # a trace shows is kv_pages / (kv_blocks * block_pages)
        ppb = got["block_pages"]
        assert 1 <= ppb and got["kv_pages"] <= got["kv_blocks"] * ppb
        assert got["kv_blocks"] == sum(-(-(-(-e // P)) // ppb)
                                       for e in walks)
        assert got["qk_pairs"] == sum(
            sum(p + i for i in range(1, q + 1)) for p, q in items)
        assert got["rows"] - got["padded_rows"] == sum(q for _p, q in items)
        # the tokens this launch picks: a finishing prompt's first, a
        # decode tick's rows (a rider's are counted at its decode tick)
        for seq in tick[4]["rids"]:
            if tick[0] == "decode_tick" or (filled[seq] == plen[seq]
                                            and not made[seq]):
                made[seq] += 1
    assert filled == plen and made == {1: 3, 2: 3} and rode > 0

    # (d) beacons
    stamps = [e[4]["stamp"] for e in rec.events if e[0] == "ffclock"]
    assert stamps and all(t_lo <= s <= t_hi for s in stamps)

    # the operator's use of the links: self time beside total
    path = rec.export_chrome_trace(str(tmp_path / "t.json"))
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert fft.main(["summarize", path]) == 0
    rows = {ln.split()[0]: ln.split() for ln in out.getvalue().splitlines()}
    assert rows["span"][2:4] == ["total_ms", "self_ms"]
    total, self_ = float(rows["decode_tick"][2]), float(rows["decode_tick"][3])
    assert 0.0 <= self_ < total
    assert float(rows["fetch"][2]) == float(rows["fetch"][3])   # a leaf


def test_warm_shape_spans_split_compile_seconds():
    """Traced, warm_launch_shapes() records one `warm_shape` span a ragged
    launch shape with what it cost, by jax's own compile phases."""
    ff, lcfg = _causal_lm()
    rec = obs.enable()
    srv = ff.serve_generation(slots=2, max_len=16, paged=True, page_size=8,
                              defer_start=True)
    try:
        catalog = srv.warm_launch_shapes()
    finally:
        srv.stop()
    obs.disable()
    shapes = catalog["entries"]["ragged_step"]["shapes"]
    warm = [e[4] for e in rec.events if e[0] == "warm_shape"]
    assert len(warm) == len(shapes)
    assert sorted((w["rows"] // w["window"], w["window"]) for w in warm) \
        == sorted((int(b), int(w)) for b, w in shapes)
    for w in warm:
        assert {"trace_s", "lower_s", "backend_s", "call_s",
                "first_run_s"} <= set(w)
        assert min(w["trace_s"], w["lower_s"], w["backend_s"],
                   w["first_run_s"]) >= 0.0
        assert w["call_s"] >= w["backend_s"]
    assert sum(w["backend_s"] for w in warm) > 0.0
