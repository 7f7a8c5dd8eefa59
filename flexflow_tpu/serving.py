"""Inference serving — the `triton/` backend analog.

The reference ships a ~13K-LoC Legion-based Triton backend (triton/README.md
:1-6): ONNX parse → partitioned model instances → request batching →
strategy-file-driven multi-GPU serving. TPU-native redesign: a served model
is ONE jit-compiled forward per padded batch size over the model's mesh
(strategies via the same ShardingViews as training); a dynamic batcher
queues requests, pads to the nearest compiled batch, runs, and splits the
results. No separate runtime — the executor's forward is the instance.

  ff = FFModel(...); ...build/compile...
  server = ff.serve(batch_sizes=(1, 4, 8), max_delay_ms=2)
  fut = server.submit(x)          # per-request async
  y = fut.result()
  server.stop()

ONNX serving parity: `serve_onnx(path, ...)` loads the model through the
ONNX frontend (the triton onnx_parser.cc analog) and serves it.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu import obs


def pick_tokens(probs_last, temps, rng):
    """Sample one token per row: greedy where temp<=0, else temperature-
    scaled categorical. Pure jnp on its arguments: the host-side jitted
    `_pick`. Row b's draw depends only on (rng, row b's logits):
    padded/idle rows never perturb live rows."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(probs_last, axis=-1).astype(jnp.int32)
    logits = jnp.log(jnp.maximum(probs_last, 1e-30)) / jnp.maximum(
        temps[:, None], 1e-6)
    sampled = jax.random.categorical(rng, logits, axis=-1).astype(
        jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def probs_rows(probs, i, r, n):
    """Of a launch's (B, W, V) probs: row r of entry i as (1, V), and row 0
    of its LAST n entries as (n, V), zero rows first when B < n: where the
    decode rows that rode a chunk's launch lie. Indices are operands, the
    rest static: one cheap program a launch shape (an eager static slice
    is one a piece index, 110 ms each on the chip, inside the window)."""
    import jax
    row = jax.lax.dynamic_slice(probs, (i, r, 0), (1, 1, probs.shape[2]))
    return row[:, 0, :], jax.numpy.pad(
        probs[-n:, 0, :], ((max(n - probs.shape[0], 0), 0), (0, 0)))


class ModelInstance:
    """One compiled forward per allowed batch size (the reference's
    per-instance compiled model, triton/src/instance.cc analog)."""

    def __init__(self, ff, batch_sizes: Sequence[int]):
        self.ff = ff
        self.batch_sizes = tuple(sorted(set(batch_sizes)))
        self._fwd = ff.executor.forward_fn()
        self._params = ff._params

    def pick_batch(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    def run(self, inputs: List[np.ndarray]) -> np.ndarray:
        """Run one already-padded batch."""
        tr, ntr = self._params
        out = self._fwd(tr, ntr, *[self.ff._device_put_batch([x])[0]
                                   for x in inputs])
        return np.asarray(out)

    def warmup(self):
        """Compile every batch size up front (instances are ready before
        the first request, like the reference's instance init)."""
        specs = [n.outputs[0] for n in self.ff.executor.input_nodes]
        for b in self.batch_sizes:
            fakes = [
                np.zeros((b,) + tuple(d.size for d in s.dims[1:]),
                         s.dtype.jnp_dtype)
                for s in specs
            ]
            self.run(fakes)


class _Request:
    __slots__ = ("inputs", "future", "n")

    def __init__(self, inputs: List[np.ndarray]):
        self.inputs = inputs
        self.n = inputs[0].shape[0]
        self.future: Future = Future()


class Server:
    """Dynamic batcher: requests queue up, are concatenated up to the
    largest compiled batch (or until `max_delay_ms` passes), run as one
    forward, and split back per request — the reference triton backend's
    scheduling core, minus the wire protocol."""

    def __init__(self, instance: ModelInstance, max_delay_ms: float = 2.0):
        self.instance = instance
        self.max_delay = max_delay_ms / 1e3
        self._q: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._running = True
        self._served = 0
        self._thread.start()

    # -- client side ----------------------------------------------------

    def submit(self, *inputs: np.ndarray) -> Future:
        """Queue one request (batch dim may be any size ≥ 1)."""
        if not self._running:  # fflint: lock-ok (admission race is benign: a stop() after this check just drains the queued future)
            raise RuntimeError("server is stopped")
        req = _Request([np.asarray(x) for x in inputs])
        self._q.put(req)
        return req.future

    def predict(self, *inputs: np.ndarray) -> np.ndarray:
        return self.submit(*inputs).result()

    def stop(self):
        self._running = False
        self._q.put(None)
        self._thread.join(timeout=10)
        self._drain()

    def _drain(self):
        """Fail any request still queued when the loop exits (a request
        racing stop() must not leave its future forever pending)."""
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                return
            if req is not None and not req.future.done():
                req.future.set_exception(RuntimeError("server stopped"))

    @property
    def requests_served(self) -> int:  # fflint: lock-ok (monotonic counter; a stale read is fine)
        return self._served

    # -- scheduler ------------------------------------------------------

    def _loop(self):
        max_b = self.instance.batch_sizes[-1]
        while self._running:
            req = self._q.get()
            if req is None:
                break
            batch = [req]
            total = req.n
            deadline = time.monotonic() + self.max_delay
            while total < max_b:
                timeout = deadline - time.monotonic()
                if timeout <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=timeout)
                except queue.Empty:
                    break
                if nxt is None:
                    self._running = False
                    break
                batch.append(nxt)
                total += nxt.n
            self._run_batch(batch, total)
        self._drain()

    def _run_batch(self, batch: List[_Request], total: int):
        b = self.instance.pick_batch(total)
        try:
            n_inputs = len(batch[0].inputs)
            cat = [np.concatenate([r.inputs[i] for r in batch])
                   for i in range(n_inputs)]
            # pad to the compiled batch (excess rows are garbage-in,
            # sliced-off-out) — may need several chunks if total > max
            outs = []
            for off in range(0, total, b):
                chunk = [c[off:off + b] for c in cat]
                pad = b - chunk[0].shape[0]
                if pad:
                    chunk = [np.concatenate([c, np.repeat(c[-1:], pad, 0)])
                             for c in chunk]
                out = self.instance.run(chunk)
                outs.append(out[:min(b, total - off)])
            full = np.concatenate(outs)
            off = 0
            for r in batch:
                r.future.set_result(full[off:off + r.n])
                off += r.n
                self._served += 1
        except Exception as e:  # propagate to every waiting client
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)


def serve(ff, batch_sizes: Sequence[int] = (1, 8), max_delay_ms: float = 2.0,
          warmup: bool = True) -> Server:
    """Create a serving endpoint for a compiled FFModel."""
    inst = ModelInstance(ff, batch_sizes)
    if warmup:
        inst.warmup()
    return Server(inst, max_delay_ms=max_delay_ms)


def serve_onnx(path: str, config=None, batch_sizes: Sequence[int] = (1, 8),
               strategy_file: Optional[str] = None,
               input_shapes: Optional[Dict[str, Sequence[int]]] = None,
               **kw) -> Tuple[Server, "object"]:
    """ONNX → served model (the triton backend's onnx_parser + strategy
    file flow, triton/src/onnx_parser.cc / strategy.cc analog). Returns
    (server, ffmodel). Only the FIRST (batch) dim may be symbolic in the
    ONNX graph; fix other dynamic dims via `input_shapes[name]`."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import CompMode, LossType
    from flexflow_tpu.frontends.onnx_model import ONNXModel
    from flexflow_tpu.model import FFModel

    from flexflow_tpu.ffconst import DataType

    cfg = config or FFConfig()
    cfg.comp_mode = CompMode.INFERENCE
    if strategy_file:
        cfg.import_strategy_file = strategy_file
    ff = FFModel(cfg)
    onnx_model = ONNXModel(path)
    # declared graph inputs (minus initializers) become framework tensors
    graph = onnx_model.model.graph
    init_names = {i.name for i in graph.initializer}
    inputs = {}
    for vi in graph.input:
        if vi.name in init_names:
            continue
        if input_shapes and vi.name in input_shapes:
            dims = list(input_shapes[vi.name])
        else:
            raw = [d.dim_value for d in vi.type.tensor_type.shape.dim]
            dims = [raw[0] or cfg.batch_size] + raw[1:]
            if any(not d for d in dims[1:]):
                raise ValueError(
                    f"ONNX input {vi.name!r} has symbolic non-batch dims "
                    f"{raw}; pass input_shapes={{'{vi.name}': (...)}}"
                )
        dt = DataType.INT32 if vi.type.tensor_type.elem_type in (6, 7) \
            else DataType.FLOAT
        inputs[vi.name] = ff.create_tensor(tuple(dims), dt, name=vi.name)
    onnx_model.apply(ff, inputs)
    ff.compile(loss_type=LossType.IDENTITY)
    return serve(ff, batch_sizes=batch_sizes, **kw), ff


# ---------------------------------------------------------------------------
# HTTP endpoint (the triton wire-protocol analog; KServe-v2-shaped JSON)


_DTYPE_TO_V2 = {"float32": "FP32", "float64": "FP64", "int32": "INT32",
                "int64": "INT64", "bool": "BOOL", "float16": "FP16"}
_V2_TO_DTYPE = {v: k for k, v in _DTYPE_TO_V2.items()}


def http_serve(server: Server, port: int = 8000, model_name: str = "model",
               generation_server=None):
    """Expose a Server over HTTP with the KServe v2 JSON surface the
    reference's triton backend speaks (triton/README.md):

      GET  /v2/health/ready                 -> 200
      GET  /v2/models/<name>               -> metadata
      GET  /v2/models/<name>/metrics       -> serving metrics JSON
      GET  /metrics                        -> Prometheus text exposition
      POST /v2/models/<name>/infer         -> {"inputs": [{"name","shape",
                                               "datatype","data"}...]}

    The JSON metrics endpoint serves the batcher's counters and — when a
    `generation_server` (serve_generation) is attached — its aggregate +
    per-request generation metrics (queue times, pages, preemptions,
    speculative acceptance rates), so operators scrape what was
    previously reachable only from Python. `GET /metrics` serves the
    SAME numbers (same MetricsRegistry + the flattened scalar counters,
    `ff_` prefix) in Prometheus text-exposition format, so a standard
    scrape config needs no JSON translation layer (docs/observability.md
    has the scrape stanza).

    Returns the ThreadingHTTPServer (serve_forever on a thread; call
    .shutdown() to stop). Stdlib-only — no server framework in the image.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    # model input order for by-name binding (KServe clients may list
    # tensors in any order; names win over positions when they match)
    input_names = [
        n.name for n in server.instance.ff.executor.input_nodes
    ]

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def _send(self, code: int, payload: dict):
            self._send_raw(code, json.dumps(payload).encode(),
                           "application/json")

        def _send_raw(self, code: int, body: bytes, ctype: str):
            try:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except OSError:
                # client went away mid-response; nothing to salvage (a
                # second status line would corrupt the stream)
                self.close_connection = True

        def do_GET(self):
            if self.path == "/v2/health/ready":
                ready = getattr(server, "_running", True)
                self._send(200 if ready else 503, {"ready": bool(ready)})
            elif self.path == f"/v2/models/{model_name}":
                meta = {
                    "name": model_name,
                    "platform": "flexflow_tpu",
                    "requests_served": server.requests_served,
                }
                # paged servers declare their numerics: the per-entry
                # compute/accum/kv dtype plan + whether the live pool
                # matches it (ff_dtype_plan_ok; numcheck's HLO arm
                # audits the same plan against the lowered programs)
                if generation_server is not None and hasattr(
                        generation_server, "_model_block"):
                    meta["model"] = generation_server._model_block()
                self._send(200, meta)
            elif self.path == f"/v2/models/{model_name}/metrics":
                payload = {
                    "server": {"requests_served": server.requests_served},
                }
                if generation_server is not None:
                    payload["generation"] = generation_server.metrics()
                self._send(200, payload)
            elif self.path == "/metrics":
                # Prometheus text exposition off the SAME registry the
                # JSON endpoint reads; the flattened scalar metrics
                # (counters the servers track outside the registry) ride
                # along so the two surfaces always agree
                scalars = {"server_requests_served":
                           float(server.requests_served)}
                if generation_server is not None:
                    gm = generation_server.metrics()
                    gm.pop("requests", None)    # per-request detail:
                    gm.pop("histograms", None)  # JSON-only; registry
                    scalars.update(obs.flatten_scalars(gm, "generation"))
                    reg = generation_server.registry
                else:
                    reg = obs.MetricsRegistry()
                self._send_raw(
                    200,
                    reg.prometheus_text(extra_scalars=scalars).encode(),
                    "text/plain; version=0.0.4")
            else:
                self._send(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):
            if self.path != f"/v2/models/{model_name}/infer":
                self._send(404, {"error": f"unknown path {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                specs = req["inputs"]
                names = [s.get("name") for s in specs]
                if (len(specs) == len(input_names) and all(names)
                        and set(names) == set(input_names)):
                    # standards path: bind tensors by name
                    specs = sorted(
                        specs, key=lambda s: input_names.index(s["name"])
                    )
                arrays = []
                for spec in specs:
                    v2dt = spec.get("datatype", "FP32")
                    if v2dt not in _V2_TO_DTYPE:
                        raise ValueError(f"unsupported datatype {v2dt!r}")
                    arrays.append(
                        np.asarray(spec["data"], dtype=_V2_TO_DTYPE[v2dt])
                        .reshape(spec["shape"])
                    )
            except Exception as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            try:
                out = np.asarray(server.predict(*arrays))
            except Exception as e:
                # inference failures are SERVER errors (5xx — retryable),
                # unlike the request-decode 400s above
                self._send(503, {"error": f"{type(e).__name__}: {e}"})
                return
            self._send(200, {
                "model_name": model_name,
                "outputs": [{
                    "name": "output0",
                    "shape": list(out.shape),
                    "datatype": _DTYPE_TO_V2.get(str(out.dtype), "FP32"),
                    "data": out.reshape(-1).tolist(),
                }],
            })

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


# ---------------------------------------------------------------------------
# continuous batching for autoregressive generation


class _GenRequest:
    __slots__ = ("prompt", "max_new", "temperature", "future", "tokens",
                 "pos", "pages", "submit_t", "admit_t", "prefill_tokens",
                 "peak_pages", "preemptions", "spec_steps", "spec_drafted",
                 "spec_accepted", "spec_emitted", "first_token_t",
                 "cached_prefill_tokens", "prefill_pos", "prefill_target",
                 "prefill_seq", "hashed_blocks", "decode_overlap_ticks",
                 "compile_s_at_submit", "first_compile_s",
                 "spilled_pages", "fetched_pages", "routed_to", "seq",
                 "window_pages", "unseen")

    def __init__(self, prompt: np.ndarray, max_new: int, temperature: float):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.future: Future = Future()
        self.tokens: List[int] = []
        # tokens picked on the device for this request and not yet taken
        # by the host: rows of launches in flight (paged/scheduler.py
        # "Launch ahead"). 0 wherever the loop waits for every pick
        self.unseen = 0
        # this server's id for the request from submission on (spans'
        # `rids`, the request log's `seq`); `rid` exists only once it
        # completes. 0 = not submitted; a handed-off request is stamped
        # again by the server that takes it
        self.seq = 0
        self.pos = 0  # next cache write position for this slot
        # paged-path bookkeeping / per-request metrics
        self.pages: List[int] = []      # pool pages held (paged only)
        # window-class pages held, by the block of rows each backs (a
        # graph with sliding-window layers only; paged/scheduler.py)
        self.window_pages: Dict[int, int] = {}
        self.submit_t = time.monotonic()
        self.admit_t: Optional[float] = None
        self.first_token_t: Optional[float] = None  # TTFT stamp
        # jit compile seconds charged between submit and first token
        # (obs.compile_tracker): splits TTFT into compile vs serve time
        self.compile_s_at_submit = 0.0
        self.first_compile_s: Optional[float] = None
        self.prefill_tokens = 0         # prompt rows actually COMPUTED
        self.cached_prefill_tokens = 0  # prompt rows served by the cache
        self.peak_pages = 0
        self.preemptions = 0
        # chunked-prefill progress (paged scheduler): rows [0, prefill_pos)
        # of prefill_seq hold valid K/V; the slot decodes only once
        # prefill_pos reaches prefill_target. hashed_blocks counts the
        # full pages already published to the prefix cache (the hash
        # chain is re-derived from seq_tokens(), so no hasher state
        # survives preemption).
        self.prefill_pos = 0
        self.prefill_target = 0
        self.prefill_seq: Optional[np.ndarray] = None
        self.hashed_blocks = 0
        self.decode_overlap_ticks = 0   # decode ticks run mid-prefill
        # speculative decoding (flexflow_tpu.spec): verify steps run for
        # this request, draft tokens proposed/accepted, tokens emitted
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        # disaggregated serving (flexflow_tpu.disagg): pages this request
        # spilled into / fetched out of the host KV tier, and which
        # router instance served it (None when unrouted)
        self.spilled_pages = 0
        self.fetched_pages = 0
        self.routed_to: Optional[str] = None

    def seq_tokens(self) -> np.ndarray:
        """prompt + generated-so-far: what a (re-)prefill must feed. For a
        fresh request this is just the prompt; for a preempted requeue it
        re-derives the full context WITHOUT mutating the prompt (folding
        tokens into the prompt double-counted them on a second
        preemption)."""
        if not self.tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    def metrics(self) -> dict:
        """Per-request serving metrics (queue time covers submit -> LAST
        admission, so a preempted request's requeue wait counts too)."""
        ttft = (self.first_token_t - self.submit_t
                if self.first_token_t is not None else None)
        m = {
            "queue_time_s": (self.admit_t - self.submit_t
                             if self.admit_t is not None else None),
            "ttft_s": ttft,
            "first_compile_s": self.first_compile_s,
            "ttft_excl_compile_s": (
                max(0.0, ttft - self.first_compile_s)
                if ttft is not None and self.first_compile_s is not None
                else ttft),
            "prefill_tokens": self.prefill_tokens,
            "cached_prefill_tokens": self.cached_prefill_tokens,
            "decode_tokens": len(self.tokens),
            "pages_held_peak": self.peak_pages,
            "preemptions": self.preemptions,
            "decode_overlap_ticks": self.decode_overlap_ticks,
        }
        if self.spec_steps:
            m.update({
                "spec_steps": self.spec_steps,
                "spec_draft_tokens": self.spec_drafted,
                "spec_accepted_tokens": self.spec_accepted,
                "spec_acceptance_rate": (
                    self.spec_accepted / self.spec_drafted
                    if self.spec_drafted else 0.0),
                "spec_accepted_tokens_per_step": (
                    self.spec_emitted / self.spec_steps),
            })
        return m


class _GenerationServerBase:
    """Shared chassis of the dense and paged generation servers: request
    queue + stop/drain contract, temperature/greedy sampling, prompt
    validation, and the learned-position-table guard — so the two decode
    paths can never drift apart on the serving surface."""

    # default cap on per-request metric records kept for metrics();
    # bounded so a long-running server (and the HTTP metrics scrape)
    # cannot grow without limit — oldest records drop first. Override
    # per server with request_record_limit.
    MAX_REQUEST_RECORDS = 1024

    def __init__(self, ff, slots: int, max_len: int,
                 eos_id: Optional[int], seed: int,
                 request_record_limit: Optional[int] = None,
                 reqlog_capacity: Optional[int] = None,
                 slo=None, slo_dump_dir: Optional[str] = None,
                 serve_strategy=None, defer_start: bool = False):
        import jax

        self.ff = ff
        # the ServeStrategy this server realizes (search.servesearch),
        # when known: its fingerprint stamps every reqlog record and the
        # /v2 metrics payload so records attribute to the strategy that
        # served them across autopilot swaps. The paged scheduler
        # derives one from its own knobs when the caller passed none.
        self.serve_strategy = serve_strategy
        self._strategy_fp: Optional[str] = None
        # defer_start=True builds the server WITHOUT launching the loop
        # thread — the drain-and-swap path warms launch shapes and
        # absorbs carried requests first, then calls start()
        self._defer_start = bool(defer_start)
        self._gc_frozen = False   # warm_launch_shapes() sets it
        # set while detach_for_swap() pauses the loop: the finally-drain
        # must NOT cancel futures that are about to be carried over
        self._detaching = False
        self.slots = int(slots)
        self.max_len = int(max_len)
        # learned-position models (GPT-2/BERT-style): serving past the
        # position table would silently clamp to the last row in-jit —
        # refuse at construction, same contract as FFModel.generate
        rows = ff.position_table_rows()
        if rows is not None and self.max_len > rows:
            raise ValueError(
                f"max_len ({self.max_len}) exceeds the model's learned "
                f"position table ({rows} rows); rebuild with a longer "
                "seq_len or lower max_len")
        self.eos_id = eos_id
        self._params = ff.serving_params()
        self._rng = jax.random.key(seed)

        # compile-event ledger (obs.compile_tracker): shared with the
        # executor's wrapped decode entry points when present, so one
        # tracker sees every jit compilation the serving path can cause
        tracker = getattr(getattr(ff, "executor", None),
                          "compile_tracker", None)
        if tracker is None:
            tracker = obs.CompileTracker()
        self._compile_tracker = tracker
        # a shared (executor-owned) tracker outlives servers: this
        # server's compile story starts here, and its warmup phase
        # begins regardless of what a previous server marked
        self._compile_events_base = tracker.compile_events_total
        tracker.mark_warmup()
        # probs_last: (B, V) — the one sampling program every decode path
        # shares (dense, paged, packed spec roots)
        self._pick = tracker.wrap("pick_tokens", jax.jit(pick_tokens),
                                  lambda args: (args[0].shape[0],))
        self._probs_rows = jax.jit(probs_rows, static_argnums=3)
        # ragged launch shape -> (pool leaves passed, leaves written in
        # place), as warm_launch_shapes saw the shape's first call
        self._pool_alias: dict = {}
        self._queue: "queue.Queue[_GenRequest]" = queue.Queue()
        self._active: List[Optional[_GenRequest]] = [None] * self.slots
        self._tokens = np.zeros((self.slots,), np.int32)
        # the paged server's device copy of it, a launch ahead of the host
        self._newest = None
        self._stop = threading.Event()
        # guards the _running/queue.put pair against a submit racing stop()
        self._lock = threading.Lock()
        self._running = True
        self._submitted = 0     # stamps _GenRequest.seq, under _lock
        self._served = 0
        self._steps = 0
        # per-request records ride a ring buffer (cumulative counters and
        # histograms are unaffected by the cap — only the per-request
        # detail list is bounded)
        limit = (int(request_record_limit) if request_record_limit
                 is not None else self.MAX_REQUEST_RECORDS)
        if limit < 1:
            raise ValueError(
                f"request_record_limit must be >= 1, got {limit}")
        self.request_record_limit = limit
        # the ONE bounded-retention code path (obs.reqlog.BoundedRing):
        # per-request metric records and the reqlog ring share it, and
        # both drop counts ride the /v2 metrics payload
        self._request_metrics = obs.BoundedRing(limit)
        # request-log flight recorder (obs.reqlog): one record per
        # completed request, on by default; capacity 0 disables it
        # (falsy NULL_REQLOG — the emit site guards on truthiness)
        self._reqlog = obs.request_log(reqlog_capacity)
        # live SLO judge (obs.slo): fed the same reqlog records; a
        # breach transition dumps the flight-recorder state
        if slo is not None and not isinstance(slo, obs.SLOMonitor):
            slo = obs.SLOMonitor(slo, dump_dir=slo_dump_dir)
        elif slo is not None and slo_dump_dir is not None:
            slo.dump_dir = slo_dump_dir
        self._slo = slo
        # always-on histograms (obs.metrics): tick latency, TTFT, queue
        # time, tokens emitted per tick. Backs BOTH the JSON metrics
        # payload and the Prometheus text endpoint.
        self.registry = obs.MetricsRegistry()
        self._h_tick = self.registry.histogram("tick_latency_s")
        self._h_prefill = self.registry.histogram("prefill_tick_s")
        self._h_ttft = self.registry.histogram("ttft_s")
        self._h_queue = self.registry.histogram("queue_time_s")
        self._h_tokens = self.registry.histogram("tokens_per_tick",
                                                 obs.COUNT_BUCKETS)
        # TTFT with the request's attributable jit-compile seconds
        # subtracted — the steady-state latency a warmed server delivers
        self._h_ttft_excl = self.registry.histogram("ttft_excl_compile_s")
        self._compile_tracker.set_registry(self.registry)
        self._g_recompiles = self.registry.gauge("steady_state_recompiles")
        self._g_jit_entries = self.registry.gauge("jit_cache_entries")
        # SLO surface (ff_slo_breaches_total / ff_goodput_ratio) exists
        # only when a target is declared — no dead series otherwise
        if self._slo is not None:
            self._c_slo_breaches = self.registry.counter(
                "slo_breaches_total")
            self._g_goodput = self.registry.gauge("goodput_ratio")
            self._g_goodput.set(1.0)
        self._thread: Optional[threading.Thread] = None

    def _start(self):
        """Subclasses call this LAST in __init__ (the loop thread must not
        observe a half-built server). A defer_start=True server skips it;
        the builder calls start() after warmup/absorption."""
        if not self._defer_start:
            self.start()

    def start(self):
        """Launch the serving loop thread. Construction does this
        automatically unless defer_start=True — the drain-and-swap path
        defers so it can warm_launch_shapes() and absorb carried
        requests against a loop that is provably not running yet."""
        if self._thread is not None:
            raise RuntimeError(f"{type(self).__name__} already started")
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # -- public API ------------------------------------------------------

    def _check_capacity(self, prompt: np.ndarray, max_new_tokens: int):
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({self.max_len})")

    def submit(self, prompt_ids: np.ndarray, max_new_tokens: int,
               temperature: float = 0.0) -> Future:
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("prompt must contain at least one token")
        self._check_capacity(prompt, max_new_tokens)
        req = _GenRequest(prompt, max_new_tokens, temperature)
        # compile-clock baseline: compile seconds accrued later, before
        # this request's first token, are ITS attributable compile cost
        req.compile_s_at_submit = self._compile_tracker.compile_seconds_total
        return self._enqueue(req)

    def _enqueue(self, req: _GenRequest) -> Future:
        with self._lock:
            if not self._running:
                raise RuntimeError(f"{type(self).__name__} is stopped")
            self._submitted += 1
            req.seq = self._submitted
            self._queue.put(req)
        return req.future

    def submit_request(self, req: _GenRequest) -> Future:
        """Enqueue an ALREADY-BUILT request — the disagg handoff path
        (disagg/workers.py): the prefill worker hands its finished
        _GenRequest (future, tokens-so-far, tier counters intact) to the
        decode worker, whose admission re-attaches the spilled pages
        through the shared host tier. Stamps the compile-clock baseline
        only for a fresh request, so a handed-off request keeps charging
        compile time against its ORIGINAL submit."""
        self._check_capacity(req.prompt, req.max_new)
        if req.compile_s_at_submit == 0.0 and not req.tokens:
            req.compile_s_at_submit = (
                self._compile_tracker.compile_seconds_total)
        return self._enqueue(req)

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 temperature: float = 0.0) -> np.ndarray:
        return self.submit(prompt_ids, max_new_tokens, temperature).result()

    def stop(self):
        with self._lock:
            self._running = False
            self._stop.set()
        if self._gc_frozen:
            import gc

            self._gc_frozen = False
            gc.unfreeze()   # warm_launch_shapes froze set-up's objects
        if self._thread is None:  # built deferred, never started
            self._drain()
            return
        self._thread.join(timeout=30)
        # drain from this thread ONLY once the loop thread is dead —
        # otherwise its finally-drain owns the cleanup and a concurrent
        # drain here would null _active slots mid-tick under the loop
        if not self._thread.is_alive():
            self._drain()

    @property
    def requests_served(self) -> int:  # fflint: lock-ok (monotonic counter; a stale read is fine)
        return self._served

    @property
    def decode_steps(self) -> int:  # fflint: lock-ok (monotonic counter; a stale read is fine)
        return self._steps

    @property
    def request_log(self):
        """The flight recorder (obs.reqlog.RequestLog, or the falsy
        NULL_REQLOG when constructed with reqlog_capacity=0). Export
        with `server.request_log.export_jsonl(path)`."""
        return self._reqlog

    @property
    def slo_monitor(self):
        """The live SLO judge (obs.slo.SLOMonitor), or None when no
        target was declared."""
        return self._slo

    @property
    def strategy_fingerprint(self) -> Optional[str]:
        """Short content hash of the ServeStrategy this server realizes
        (None when unknown — the dense server without an explicit
        strategy). Stamped into reqlog records and /v2 metrics so
        post-swap records segment by the strategy that served them."""
        if self._strategy_fp is None and self.serve_strategy is not None:
            self._strategy_fp = self.serve_strategy.fingerprint()
        return self._strategy_fp

    def metrics(self) -> dict:  # fflint: lock-ok (relaxed metrics snapshot; int reads are atomic, staleness is fine for scraping)
        """Aggregate serving metrics + per-request records of the last
        `request_record_limit` COMPLETED requests (subclasses extend:
        paged adds pool/preemption counters, speculative adds acceptance
        rates) + the registry's histograms (tick latency, TTFT — with
        p50/p95/p99 estimates). This dict is what http_serve's
        /v2/models/<name>/metrics endpoint serves; the same registry
        backs the Prometheus `GET /metrics` endpoint."""
        entries = self.jit_cache_entries()
        snap = self._compile_tracker.snapshot(self._compile_events_base)
        self._g_recompiles.set(snap["steady_state_recompiles"])
        self._g_jit_entries.set(entries)
        snap["jit_cache_entries"] = entries
        out = {
            "requests_served": self._served,
            "decode_steps": self._steps,
            "requests": list(self._request_metrics),
            "request_records_dropped": self._request_metrics.dropped,
            "reqlog": {
                "enabled": bool(self._reqlog),
                "records": len(self._reqlog),
                "capacity": self._reqlog.capacity,
                "dropped": self._reqlog.dropped,
            },
            "compile": snap, "weights": self._weights,
            "histograms": self.registry.to_json(),
        }
        if self.serve_strategy is not None:
            out["strategy"] = {
                "fingerprint": self.strategy_fingerprint,
                "knobs": self.serve_strategy.to_json(),
            }
        if self._slo is not None:
            out["slo"] = self._slo.snapshot()
        return out

    def jit_cache_entries(self) -> int:
        """Jitted-callable memos alive for this server (the
        ff_jit_cache_entries gauge): the executor's bounded caches plus
        the server's own sampling program."""
        ex = getattr(self.ff, "executor", None)
        n = ex.jit_cache_entries() if hasattr(ex, "jit_cache_entries") else 0
        return n + 1  # _pick

    def compile_events(self) -> list:
        """Compile events recorded during THIS server's lifetime —
        the input analysis.shapecheck.check_soundness diffs against the
        catalog (a shared executor tracker also carries earlier
        servers' events; those are not this server's story)."""
        return self._compile_tracker.observed(self._compile_events_base)

    # -- launch-shape warmup (analysis.shapecheck runtime arm) -----------

    def shape_config(self) -> dict:
        """enumerate_catalog kwargs describing THIS server's launch-shape
        space; subclasses override (paged adds pool geometry, spec adds
        tree width). The dense server's space is the slot-decode shape
        plus the pow2 admission-prefill buckets."""
        return {"slots": self.slots, "max_len": self.max_len,
                "paged": False}

    def warm_launch_shapes(self, catalog: Optional[dict] = None,
                           mark_steady: bool = True) -> dict:
        """Pre-compile every launch shape this server can dispatch
        (executor.warm_launch_shapes against the shapecheck catalog, then
        the sampling program at its catalog widths), and — by default —
        mark the compile tracker steady-state: any compilation after this
        returns counts as a `steady_state_recompiles` event, the number
        the CI soundness gate pins at zero. Call before taking traffic;
        returns the catalog served (callers hand it to
        analysis.shapecheck.check_soundness)."""
        import jax
        import jax.numpy as jnp

        if catalog is None:
            from flexflow_tpu.analysis.shapecheck import enumerate_catalog

            catalog = enumerate_catalog(**self.shape_config())

        def on_probs(p):
            # what a tick runs on a launch's probs, at that shape: the
            # rows program (a completing prefill's last row, the rows of
            # decode items behind a chunk) and the decode tick's eager slice
            self._probs_rows(p, np.int32(0), np.int32(0), self.slots)
            if p.shape[:2] == (self.slots, 1):
                p[:, -1, :]

        info = self.ff.executor.warm_launch_shapes(
            catalog, params=self._params, on_probs=on_probs,
            newest=self._newest)  # fflint: lock-ok (warm-up: called before traffic; the loop rebinds it only while launching)
        self._pool_alias = info["pool_alias"]
        self._warm_riders(info.get("probs_ref"))
        # the rng chain's split: a host-made key first, its own (committed)
        # output from then on; throwaway keys, as below
        key, _ = jax.random.split(jax.random.key(0))
        key, _ = jax.random.split(key)
        probs_ref = info.get("probs_ref")
        if probs_ref is not None:
            # serve-time pick inputs are SLICES of launch outputs —
            # committed, with the launch's output sharding (part of the
            # jit cache key) — so warm from slices of the real probs the
            # executor warm just produced, not from synthetic arrays
            ref = (probs_ref[:, -1, :] if probs_ref.ndim == 3
                   else probs_ref)
            picks = catalog.get("entries", {}).get(
                "pick_tokens", {}).get("shapes", ())
            for (b,) in picks:  # fflint: host-ok (one-time warmup)
                b = int(b)
                probs = (ref[:b] if int(ref.shape[0]) >= b
                         else jnp.concatenate([ref[:1]] * b))
                temps = jnp.zeros((b,), jnp.float32)
                # a throwaway key: warming must not consume the serving
                # rng chain (greedy/sampled token identity)
                self._pick(probs, temps, jax.random.key(0))
        if mark_steady:
            self._compile_tracker.mark_steady_state()
        # what set-up made (jax's own objects, 97 traced programs: millions
        # of containers) lives as long as the server. Left where the cyclic
        # collector walks it, one generation-2 collection stops the loop
        # for 0.85 s (measured on the chip, PERF.md section 6, PR 29), a
        # couple of times a minute. Collect now, then move what is left
        # out of the collector's reach; stop() gives it back
        import gc

        gc.collect()
        gc.freeze()
        self._gc_frozen = True
        return catalog

    def _warm_riders(self, probs):
        """Hook: warm what a subclass runs on `probs_rows`' output that
        no launch shape keys (the paged server's `rows_at`)."""

    # -- shared scheduler pieces -----------------------------------------

    @staticmethod
    def _bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def _pick_first_token(self, req: _GenRequest, row_probs):
        """Pick a request's FIRST token from its last real prompt row's
        probs, ON THE DEVICE: the (1,) pick, not yet fetched. ONE
        implementation shared by the dense admission prefill and the
        paged chunked prefill, so the rng/_pick discipline (and with it
        greedy dense-vs-paged token identity) can never drift."""
        import jax
        import jax.numpy as jnp

        with obs.span("sample"):
            self._rng, sub = jax.random.split(self._rng)
            return self._pick(
                row_probs, jnp.full((1,), req.temperature, jnp.float32),
                sub)

    def _sample_first_token(self, slot: int, req: _GenRequest, row_probs):
        """Pick the first token, wait for it, append it and stamp TTFT
        (the dense server's admission; the paged loop picks at dispatch
        and takes the value a launch later, `_retire`)."""
        picked = self._pick_first_token(req, row_probs)
        with obs.span("fetch") as sp:
            # the host's wait for the device: the launch, the pick, the copy
            fetched = np.asarray(picked)
            if sp:
                sp.set(bytes=int(fetched.nbytes))
        req.pos = len(req.seq_tokens())  # before the append below
        self._take_first_token(slot, req, int(fetched[0]))

    def _take_first_token(self, slot: int, req: _GenRequest, tok: int):
        """Append a request's first token and stamp TTFT; `req.pos` is
        the caller's."""
        req.tokens.append(tok)
        self._tokens[slot] = tok
        if req.first_token_t is None:
            req.first_token_t = time.monotonic()
            req.first_compile_s = max(
                0.0, self._compile_tracker.compile_seconds_total
                - req.compile_s_at_submit)

    def _admit_common(self, req: _GenRequest, slot: int, padded_len: int,
                      scatter_rows):
        """Bucketed prefill + first-token sample, shared by the dense and
        paged admits so their sampling/rng discipline can never drift:
        pad the prompt right (pad rows land at kpos > the slot's qpos, so
        they are masked until overwritten by real decode writes), hand
        the prefill K/V rows to `scatter_rows` (dense slot-scatter or
        paged page-scatter), pick the first token from the last REAL
        prompt position, and stamp the request's admission bookkeeping."""
        import jax.numpy as jnp

        tr, ntr = self._params
        seq = req.seq_tokens()
        n = len(seq)
        padded = np.zeros((1, padded_len), np.int32)
        padded[0, :n] = seq
        probs, upd = self._prefill_step(tr, ntr, self._prefill_caches, 0,
                                        jnp.asarray(padded))
        scatter_rows(upd)
        req.admit_t = time.monotonic()
        req.prefill_tokens += n
        self._sample_first_token(slot, req, probs[:, n - 1, :])
        self._active[slot] = req

    # -- request log (obs.reqlog) ----------------------------------------

    def _prefix_chain(self, req: _GenRequest) -> tuple:
        """Content-hash prefix chain for the reqlog record (never the raw
        tokens). The dense path has no page pool to derive one from; the
        paged scheduler overrides with the pool's sha1 page-block chain."""
        return ()

    def _reqlog_kv_dtype(self) -> str:
        """KV storage dtype for the reqlog record; the paged scheduler
        overrides with the pool's resolved dtype name."""
        return "dense"

    def _reqlog_record(self, req: _GenRequest, m: dict,
                       done_t: float) -> dict:
        """One flight-recorder record per completed request
        (obs.reqlog's schema): lifecycle stamps on the span monotonic
        clock (a missing stamp collapses forward to done, same rule as
        TraceRecorder.record_request), prompt length + prefix chain,
        sampling params, kv dtype, spec/preemption/page counters, and
        the per-phase breakdown the stamps imply."""
        admit_t = req.admit_t if req.admit_t is not None else done_t
        first_t = (req.first_token_t if req.first_token_t is not None
                   else done_t)
        rec = {
            "rid": self._served + 1,
            "seq": req.seq,
            "label": f"req {self._served + 1}",
            "submit_ns": int(req.submit_t * 1e9),
            "admit_ns": int(admit_t * 1e9),
            "first_token_ns": int(first_t * 1e9),
            "done_ns": int(done_t * 1e9),
            "prompt_tokens": int(len(req.prompt)),
            "prefix_chain": list(self._prefix_chain(req)),
            "temperature": req.temperature,
            "max_new_tokens": req.max_new,
            "kv_dtype": self._reqlog_kv_dtype(),
            "decode_tokens": m["decode_tokens"],
            "prefill_tokens": m["prefill_tokens"],
            "cached_prefill_tokens": m["cached_prefill_tokens"],
            "pages_held_peak": m["pages_held_peak"],
            "preemptions": m["preemptions"],
            "spec_steps": m.get("spec_steps", 0),
            "spec_draft_tokens": m.get("spec_draft_tokens", 0),
            "spec_accepted_tokens": m.get("spec_accepted_tokens", 0),
            # disagg fields — additive, so the schema stays
            # ff.reqlog/v1-compatible (readers ignore unknown keys)
            "spilled_pages": req.spilled_pages,
            "fetched_pages": req.fetched_pages,
            "routed_to": req.routed_to,
            "phases": {
                "queue_s": max(0.0, admit_t - req.submit_t),
                "prefill_s": max(0.0, first_t - admit_t),
                "decode_s": max(0.0, done_t - first_t),
            },
        }
        fp = self.strategy_fingerprint
        if fp is not None:
            rec["strategy"] = fp
        return rec

    def _release_slot(self, slot: int, req: _GenRequest,
                      completed: bool = False):
        """Subclass hook: reclaim per-slot resources (paged frees pages).
        `completed` distinguishes a finished request from a cancellation
        (stop()/_drain) — the finish criteria live ONLY in
        _finish_if_done. Completed requests record their per-request
        metrics (cancellations are not records)."""
        if completed:
            done_t = time.monotonic()
            m = req.metrics()
            self._request_metrics.append(m)  # BoundedRing: counts drops
            if m["ttft_s"] is not None:
                self._h_ttft.observe(m["ttft_s"])
            if m["ttft_excl_compile_s"] is not None:
                self._h_ttft_excl.observe(m["ttft_excl_compile_s"])
            if m["queue_time_s"] is not None:
                self._h_queue.observe(m["queue_time_s"])
            # flight recorder + SLO judge share one record build, and
            # neither allocates when both are off (NULL_REQLOG is falsy)
            if self._reqlog or self._slo is not None:
                record = self._reqlog_record(req, m, done_t)
                self._reqlog.log(record)
                if self._slo is not None:
                    tripped = self._slo.observe(record)
                    self._g_goodput.set(self._slo.goodput)
                    if tripped:
                        self._c_slo_breaches.inc()
                        self._slo.dump(
                            reqlog=self._reqlog,
                            recorder=obs.recorder(),
                            metrics=self.metrics,
                            strategy=(self.serve_strategy.to_json()
                                      if self.serve_strategy is not None
                                      else None),
                            compile_snapshot=self._compile_tracker.snapshot(
                                self._compile_events_base))
            rec = obs.recorder()
            if rec is not None:
                # lifecycle track (queued→prefill→decode) from the same
                # monotonic clock the spans use
                rec.record_request(req.submit_t, req.admit_t,
                                   req.first_token_t, done_t,
                                   label=f"req {self._served + 1}", attrs=m)
        if self._active[slot] is req:
            self._active[slot] = None

    def _finished(self, req: _GenRequest) -> bool:
        """The finish criteria, in ONE place: its count, or the EOS."""
        return len(req.tokens) >= req.max_new or (
            self.eos_id is not None and bool(req.tokens)
            and req.tokens[-1] == self.eos_id)

    def _finish_if_done(self, slot: int,
                        req: Optional[_GenRequest] = None):
        """Complete the slot's request if it is done (`req`: a request
        that already left the slot, the paged server's last token in
        flight)."""
        req = req or self._active[slot]
        if req is None or not self._finished(req):
            return
        self._release_slot(slot, req, completed=True)
        self._served += 1
        req.future.set_result(np.asarray(req.tokens, np.int32))

    def _loop(self):
        try:
            self._loop_body(*self._params)
        finally:
            # runs on ANY exit — including a decode-step exception — so
            # blocked callers always unblock instead of hanging forever
            self._drain()

    # -- drain-and-swap (serving_autopilot) ------------------------------

    def detach_for_swap(self) -> List["_GenRequest"]:
        """Pause the serving loop WITHOUT cancelling futures and hand
        back every request still owed a result, in service order:
        mid-flight requests first (oldest first — re-admission preserves
        their priority), then whatever was queued. The drain-and-swap
        half that makes 'zero requests dropped' literal: each returned
        _GenRequest keeps its Future, its prompt, and every token it has
        already decoded (seq_tokens()), so a successor server resumes it
        via absorb_requests() and greedy streams stay token-identical.
        This server is stopped afterwards — only its pool/caches remain
        adoptable (PagedGenerationServer.adopt_pool_from)."""
        with self._lock:
            self._running = False
            self._detaching = True
            self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                with self._lock:
                    self._detaching = False
                raise RuntimeError(
                    "serving loop did not pause within 30s — refusing to "
                    "detach requests from a live loop")
        carried = self._detach_active()
        while True:
            try:
                carried.append(self._queue.get_nowait())
            except queue.Empty:
                break
        return carried

    def _detach_active(self) -> List["_GenRequest"]:
        """Subclass hook: pull mid-flight requests out of their slots
        without cancelling them (the paged scheduler also publishes
        tails and frees pages so the successor can re-attach). Only
        called with the loop provably stopped."""
        carried: List[_GenRequest] = []
        for s in range(self.slots):
            req = self._active[s]
            if req is not None:
                self._active[s] = None
                carried.append(req)
        return carried

    def _loop_body(self, tr, ntr):
        raise NotImplementedError

    @property
    def _weights(self) -> dict:
        """metrics()'s "weights": the bytes of the model's own tree, of
        the tree this server's launches are handed (FFModel.
        serving_params) and the leaves stored narrower in it."""
        from flexflow_tpu.runtime.serving_weights import weight_stats

        return weight_stats(self.ff._params, self._params)

    def _drain(self):
        """Cancel whatever is still queued or mid-decode so callers
        unblock — a truncated sequence must not look like a completed one.
        Runs on the loop thread at exit AND on the stop() caller's thread
        after join, so a submit racing stop() still gets resolved.
        During a drain-and-swap detach the successor server owns every
        pending future, so cancellation stands down."""
        if self._detaching:
            return
        for s in range(self.slots):
            req = self._active[s]
            if req is not None:
                self._release_slot(s, req)
                if not req.future.done():
                    req.future.cancel()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                req.future.cancel()


class GenerationServer(_GenerationServerBase):
    """Continuous batching over the KV-cache decode path (beyond the
    reference triton/ backend, which serves stateless forwards only).

    A fixed pool of `slots` shares one jitted single-token decode step with
    PER-SLOT cache positions (ops/jax_ops.py cached-attention vector-pos
    path). Each tick admits queued requests into free slots (one bucketed
    prefill per admission scatters the prompt's K/V into the slot's cache
    rows), then advances every active slot one token. Finished sequences
    (EOS or their max_new_tokens) free their slot immediately — no
    batch-drain barrier, the defining property of continuous batching.

    Each slot's cache is a DENSE max_len buffer; for HBM that scales with
    tokens in flight instead of slots x max_len, see
    flexflow_tpu.paged.PagedGenerationServer (serve_generation(paged=True)).
    """

    def __init__(self, ff, slots: int = 4, max_len: int = 512,
                 eos_id: Optional[int] = None, seed: int = 0,
                 request_record_limit: Optional[int] = None,
                 reqlog_capacity: Optional[int] = None,
                 slo=None, slo_dump_dir: Optional[str] = None,
                 serve_strategy=None, defer_start: bool = False):
        import jax

        super().__init__(ff, slots, max_len, eos_id, seed,
                         request_record_limit=request_record_limit,
                         reqlog_capacity=reqlog_capacity,
                         slo=slo, slo_dump_dir=slo_dump_dir,
                         serve_strategy=serve_strategy,
                         defer_start=defer_start)
        ex = ff.executor
        self._step = ex.decode_fn()
        self._prefill_step = self._step  # one fn, two input shapes
        self._caches = ex.init_kv_cache(self.slots, self.max_len)
        # one-slot prefill caches per bucketed prompt length share the big
        # pool's dtype/shape suffix, so rows scatter straight in
        self._prefill_caches = ex.init_kv_cache(1, self.max_len)

        @jax.jit
        def scatter_slot(big, row, slot):
            return jax.tree.map(lambda b, r: b.at[slot].set(r[0]), big, row)

        self._scatter = scatter_slot
        self._start()

    # -- scheduler loop --------------------------------------------------

    def _admit(self, req: _GenRequest, slot: int):
        """Bucketed prefill into `slot` (_admit_common), scattering the
        one-slot prefill cache's K/V rows into the slot's dense rows."""

        def scatter(upd):
            for key, rows in upd.items():
                self._caches[key] = self._scatter(self._caches[key], rows,
                                                  slot)

        self._admit_common(
            req, slot,
            min(self._bucket(len(req.seq_tokens())), self.max_len),
            scatter)
        self._finish_if_done(slot)

    def _loop_body(self, tr, ntr):
        import jax
        import jax.numpy as jnp

        while not self._stop.is_set():
            # admission: fill every free slot from the queue
            admitted = False
            with obs.span("admit") as sp:
                n_admitted = 0
                for slot in range(self.slots):
                    if self._active[slot] is not None:
                        continue
                    try:
                        req = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    self._admit(req, slot)
                    admitted = True
                    n_admitted += 1
                if sp and n_admitted:
                    sp.set(admitted=n_admitted)
            live = [s for s in range(self.slots) if self._active[s] is not None]
            if not live:
                if not admitted:
                    time.sleep(0.001)
                continue
            # one decode tick for the whole pool (idle slots compute too —
            # fixed shapes keep the step compiled once)
            t0 = time.monotonic()
            with obs.span("decode_tick") as sp:
                if sp:
                    sp.set(live=len(live))
                pos = np.array([self._active[s].pos if self._active[s] else 0
                                for s in range(self.slots)], np.int32)
                probs, upd = self._step(tr, ntr, self._caches, jnp.asarray(pos),  # fflint: host-ok (per-tick batch transfer)
                                        jnp.asarray(self._tokens)[:, None])  # fflint: host-ok (per-tick batch transfer)
                self._caches = upd
                temps = np.array([self._active[s].temperature if self._active[s]
                                  else 0.0 for s in range(self.slots)], np.float32)
                self._rng, sub = jax.random.split(self._rng)
                toks = np.asarray(self._pick(probs[:, -1, :],
                                             jnp.asarray(temps), sub))  # fflint: host-ok (per-tick batch transfer)
                self._steps += 1
                for s in live:
                    req = self._active[s]
                    req.pos += 1
                    req.tokens.append(int(toks[s]))
                    self._tokens[s] = toks[s]
                    self._finish_if_done(s)
            dt = time.monotonic() - t0
            self._h_tick.observe(dt)
            self._h_tokens.observe(len(live))
            led = obs.ledger()
            if led is not None:
                led.record("decode", dt, batch=len(live))


# what a graph's way of remembering makes a serving option unable to do:
# (is it such a graph, the options refused on it, why). ONE table of option
# names (`_refuse_unsupported`); a graph of several kinds (state layers
# beside a latent one) is refused by the first that objects
_ALL_BUT_PREFIX = ("paged=False", "kv_dtype", "speculate", "host_tier",
                   "kv_quant_canary", "serve_strategy", "search_budget")
_GRAPH_KINDS = (
    (lambda ex: bool(ex.state_layers()),
     _ALL_BUT_PREFIX + ("prefix_cache",),
     "state layers (delta-rule linear attention, `kda_attention`, or a "
     "Mamba-2 state-space mixer, `mamba2`): a layer's memory is ONE "
     "recurrent state a slot, which no prefix-cache hit can restore "
     "(prefix_cache=True is the default: pass False), no tree verify can "
     "roll back, no host tier or int8 pool holds, and "
     "which the dense server and the strategy search know nothing of"),
    (lambda ex: bool(ex.window_rows()),
     _ALL_BUT_PREFIX + ("prefix_cache",),
     "sliding-window attention layers: a window layer's pages behind the "
     "window are released, so a prefix-cache hit (prefix_cache=True is the "
     "default: pass False) would map rows that are gone; the dense cache, "
     "the int8 scale blocks, tree verify, the host tier's payloads and "
     "the strategy search's pricing all assume ONE table a request"),
    (lambda ex: any(n.op_type.value == "latent_attention" for n in ex.topo),
     _ALL_BUT_PREFIX,
     "latent attention: its page pool holds one [c_kv | k_r] row a token, "
     "not per-head K and V (the dense cache, the int8 scale sidecar, tree "
     "verify's commit, the host tier's page payloads and the strategy "
     "search's pricing all assume K/V pools)"),
    (lambda ex: any(getattr(n.attrs, "index_heads", 0) for n in ex.topo),
     _ALL_BUT_PREFIX + ("prefix_cache",),
     "sparse latent attention (an indexer over pooled keys): a page holds "
     "one pooled key a block of tokens beside the latent rows, "
     "accumulated in place while the block fills, so a prefix-cache hit "
     "(prefix_cache=True is the default: pass False) that shares a "
     "partial page would add to a row two requests read; the dense "
     "cache has no pooled keys, the int8 scale sidecar and its canary no "
     "per-head row to scale, tree verify's commit would copy latent rows "
     "and leave the pooled sums of the rejected branch behind, the host "
     "tier's payloads carry K/V pools, and the strategy search prices a "
     "walk of every page, not of the blocks a row keeps"),
)


def _refuse_unsupported(ff, *, paged, prefix_cache, kv_dtype, speculate,
                        host_tier, kv_quant_canary, serve_strategy,
                        search_budget) -> None:
    """A graph with latent attention, sliding-window layers or state
    layers is served by the paged per-tick server (chunked prefill,
    packed launches, preemption and launch-ahead included). Every option
    whose code reads what such a graph does not keep, or has not been run
    on it, is refused BY NAME here rather than left to misread it
    (docs/paged.md "Two classes of pages", "A state a slot")."""
    asked = {
        "paged=False": not paged,
        "prefix_cache": bool(prefix_cache),
        "kv_dtype": kv_dtype not in ("auto", "bf16", "fp16", "fp32"),
        "speculate": speculate is not None,
        "host_tier": host_tier is not None and host_tier != 0,
        "kv_quant_canary": bool(kv_quant_canary),
        "serve_strategy": serve_strategy is not None,
        "search_budget": search_budget is not None,
    }
    for is_kind, refused, why in _GRAPH_KINDS:
        bad = [name for name in asked if asked[name] and name in refused]
        if bad and is_kind(ff.executor):
            raise ValueError(
                f"serve_generation option(s) {bad} are not supported on a "
                f"graph with {why}")


def serve_generation(ff, slots: int = 4, max_len: int = 512,
                     eos_id: Optional[int] = None, seed: int = 0,
                     paged: bool = False, page_size: int = 64,
                     num_pages: Optional[int] = None,
                     preemption: bool = True,
                     prefix_cache: bool = True,
                     prefill_chunk: int = 64,
                     speculate=None,
                     request_record_limit: Optional[int] = None,
                     kv_dtype: str = "auto",
                     serve_strategy=None,
                     search_budget: Optional[int] = None,
                     traffic="smoke",
                     reqlog_capacity: Optional[int] = None,
                     slo=None,
                     slo_dump_dir: Optional[str] = None,
                     kv_quant_canary: Optional[int] = None,
                     defer_start: bool = False,
                     host_tier=None,
                     num_pages_window: Optional[int] = None
                     ) -> "_GenerationServerBase":
    """Continuous-batching generation endpoint over a compiled causal-LM
    FFModel (KV-cache decode path required — see FFModel.generate).

    `paged=True` serves through the block-paged KV cache
    (flexflow_tpu.paged): HBM scales with the page pool (`num_pages` x
    `page_size` tokens shared by all requests) instead of
    slots x max_len, admission is by free-page budget, and page pressure
    preempts+requeues the youngest request (`preemption=False` queues
    instead). Dense and paged paths share sampling, the position-table
    guard, and the submit/stop contract.

    `prefix_cache=True` (paged only) content-addresses pool pages by a
    hash chain over page-aligned token blocks: requests sharing a prompt
    prefix map the SAME physical pages (refcounted; copy-on-write on a
    shared partial tail), completed/preempted requests leave their pages
    behind as LRU-cached hits, and only the uncached suffix is computed.
    Prefill runs CHUNKED inside the decode loop — at most
    `prefill_chunk` prompt tokens per tick — so long prompts admit
    without stalling in-flight decodes. Greedy output is token-identical
    with the cache on or off.

    `speculate=SpecConfig(...)` (requires paged=True) turns each decode
    tick into a speculative TREE-VERIFY step (flexflow_tpu.spec): a
    drafter proposes a token tree, one forward pass scores every node,
    and the longest verified path commits — greedy output stays
    token-identical to the non-speculative paged path while emitting up
    to depth+1 tokens per step.

    A paged tick packs its mixed work — decode rows, chunk pieces,
    drafted trees — into ragged launches of the one paged-attention
    step, skipping idle slots and padding (docs/paged.md "Ragged work
    packing"; the `padding_waste_ratio` metric counts what is left).

    `request_record_limit` bounds how many completed requests keep their
    per-request metric record (default _GenerationServerBase
    .MAX_REQUEST_RECORDS); cumulative counters and histograms are
    unaffected.

    `kv_dtype` (paged only) sets the KV pool's storage dtype: "auto"
    (default) pools at the model dtype; "int8" stores QUANTIZED pages
    with per-(page, head) scales and dequant-on-load in both attention
    paths (docs/paged.md "Quantized KV pages") — the same HBM budget
    holds ~4x the fp32 pages, at a bounded greedy logit tolerance;
    "bf16"/"fp16"/"fp32" are plain storage casts.

    `search_budget=N` runs the serving-strategy search
    (flexflow_tpu.search.servesearch, docs/search.md) for N anneal
    iterations against the `traffic` profile (a name from
    search/traffic.py or a TrafficProfile) and serves the winning
    strategy; `serve_strategy` applies a known ServeStrategy (or its
    to_json() dict, e.g. from `tools/servesearch.py search`) directly.
    Either overrides the paged/page_size/prefill_chunk/
    num_pages/speculate knobs wholesale — passing an
    explicit `speculate` alongside is an error, the strategy already
    decides speculation.

    `reqlog_capacity` sizes the request-log flight recorder
    (obs.reqlog): one record per completed request — lifecycle stamps,
    prompt length + prefix-hash chain (never raw tokens), sampling
    params, spec/preemption counters. On by default (None -> 4096
    records); 0 disables it with the same no-op discipline as
    `obs.span`. Export with `server.request_log.export_jsonl(path)`;
    replay with `servesearch search --replay` / `fftrace replay`.

    `slo=SLOTarget(...)` (or its dict form) arms the live SLO monitor
    (obs.slo): sliding-window TTFT / seconds-per-token p95 against the
    declared target, goodput gauge (`ff_goodput_ratio`), and a breach
    counter (`ff_slo_breaches_total`). On an ok->breach transition the
    flight-recorder state (reqlog tail, Chrome-trace tail, metrics
    snapshot) is dumped under `slo_dump_dir` when one is given.

    `kv_quant_canary=N` (paged only) samples the fp32 shadow-cache
    divergence probe onto every Nth admitted request: the
    `kv_quant_error` gauge tracks quantization drift in production at
    1/N cost instead of requiring the all-requests
    FF_TPU_KV_QUANT_DEBUG mode (docs/paged.md). 0/None disables; env
    FF_TPU_KV_QUANT_CANARY supplies a default.

    `defer_start=True` builds the server without starting its loop —
    the drain-and-swap handoff warms shapes, adopts the predecessor's
    pool and absorbs its carried requests before calling .start()
    (docs/serving.md, "Autopilot & drain-and-swap").

    `host_tier` (paged only) attaches a host-memory KV tier
    (flexflow_tpu.disagg, docs/disaggregation.md): pass a page capacity
    (int) or a `HostTier` instance — SHARING one instance between two
    servers is the prefill/decode KV-transfer channel. Pool evictions
    spill full pages to host RAM instead of dropping them, and prefix
    lookups transparently fetch them back; greedy output stays
    token-identical.

    A graph with SLIDING-WINDOW attention layers (paged only) is served
    from two classes of pages (docs/paged.md "Two classes of pages"):
    `num_pages` sizes the full layers' class and `num_pages_window` the
    window layers' (default slots x (window + prefill_chunk + a page));
    pass `prefix_cache=False`, and see `_refuse_unsupported` for
    the options such a graph refuses by name. A graph with STATE layers
    (linear attention) is served the same way, its states a slot beside
    the pages (docs/paged.md "A state a slot"), and refuses the same."""
    _refuse_unsupported(
        ff, paged=paged, prefix_cache=prefix_cache, kv_dtype=kv_dtype,
        speculate=speculate, host_tier=host_tier,
        kv_quant_canary=kv_quant_canary, serve_strategy=serve_strategy,
        search_budget=search_budget)
    if search_budget is not None and serve_strategy is None:
        from flexflow_tpu.search.servesearch import search_serve_strategy

        serve_strategy = search_serve_strategy(
            ff, traffic=traffic, budget=int(search_budget), slots=slots,
            max_len=max_len).best
    if serve_strategy is not None:
        from flexflow_tpu.serve_strategy import ServeStrategy

        if isinstance(serve_strategy, dict):
            serve_strategy = ServeStrategy.from_json(serve_strategy)
        if speculate is not None:
            raise ValueError(
                "serve_strategy already decides speculation — drop the "
                "explicit speculate= argument")
        kw = serve_strategy.to_server_kwargs(slots, max_len)
        paged = True
        page_size = kw["page_size"]
        prefill_chunk = kw["prefill_chunk"]
        speculate = kw["speculate"]
        kv_dtype = kw["kv_dtype"]
        if kw["num_pages"] is not None:
            num_pages = kw["num_pages"]
        # the strategy's host-tier capacity applies only when the caller
        # did not hand us a tier of their own (a shared disagg tier wins)
        if host_tier is None and kw["host_tier"] is not None:
            host_tier = kw["host_tier"]
    if speculate is not None:
        if not paged:
            raise ValueError(
                "speculative decoding rides the paged KV cache (rollback "
                "is a position rewind, not a cache copy); pass paged=True")
        from flexflow_tpu.spec.server import SpeculativePagedServer

        return SpeculativePagedServer(
            ff, speculate, slots=slots, max_len=max_len, eos_id=eos_id,
            seed=seed, page_size=page_size, num_pages=num_pages,
            preemption=preemption, prefix_cache=prefix_cache,
            prefill_chunk=prefill_chunk,
            request_record_limit=request_record_limit,
            kv_dtype=kv_dtype, reqlog_capacity=reqlog_capacity,
            slo=slo, slo_dump_dir=slo_dump_dir,
            kv_quant_canary=kv_quant_canary,
            serve_strategy=serve_strategy, defer_start=defer_start,
            host_tier=host_tier)
    if paged:
        from flexflow_tpu.paged.scheduler import PagedGenerationServer

        return PagedGenerationServer(
            ff, slots=slots, max_len=max_len, eos_id=eos_id, seed=seed,
            page_size=page_size, num_pages=num_pages, preemption=preemption,
            prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
            request_record_limit=request_record_limit,
            kv_dtype=kv_dtype, reqlog_capacity=reqlog_capacity,
            slo=slo, slo_dump_dir=slo_dump_dir,
            kv_quant_canary=kv_quant_canary,
            serve_strategy=serve_strategy, defer_start=defer_start,
            host_tier=host_tier, num_pages_window=num_pages_window)
    if kv_dtype != "auto":
        raise ValueError(
            "kv_dtype rides the paged KV pool; pass paged=True")
    if kv_quant_canary:
        raise ValueError(
            "kv_quant_canary probes the paged KV pool's quantization "
            "error; pass paged=True")
    if host_tier is not None and host_tier != 0:
        raise ValueError(
            "host_tier spills the paged KV pool's content-addressed "
            "pages; pass paged=True")
    return GenerationServer(ff, slots=slots, max_len=max_len, eos_id=eos_id,
                            seed=seed,
                            request_record_limit=request_record_limit,
                            reqlog_capacity=reqlog_capacity,
                            slo=slo, slo_dump_dir=slo_dump_dir,
                            serve_strategy=serve_strategy,
                            defer_start=defer_start)
