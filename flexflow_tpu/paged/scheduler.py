"""Continuous-batching scheduler over the paged KV cache.

Replaces the dense GenerationServer's slot-only admission with admission
by FREE-PAGE BUDGET: a request is admitted when a decode slot is free
AND the pool can hold its prompt's pages; it grows one page at a time as
it decodes; page pressure preempts the youngest other request (its pages
are freed and it requeues at the FRONT of the queue with prompt +
generated prefix). EOS/max-new free pages and slot immediately. All
bookkeeping is host numpy; the jitted decode step sees only int32 page
tables and positions, so it compiles ONCE for the (slots, max_pages)
shape.

PREFIX CACHING (prefix_cache=True): admission first maps the longest
content-addressed prefix of the prompt from the pool's hash index —
full pages are SHARED by refcount, a partially filled tail page is
cloned copy-on-write — and only the uncached suffix is computed.
Completed/preempted requests leave their pages behind as dead-but-
cached LRU entries, so a preempted request's resume re-attaches its own
K/V instead of recomputing it.

CHUNKED PREFILL: the uncached suffix is computed `prefill_chunk` tokens
per tick straight into pool pages (Executor.ragged_step_fn — no
dense staging cache), INSIDE the decode loop: each tick advances
mid-prefill slots by one budgeted chunk and the decoding slots by one
token IN THE SAME LAUNCH, so a long prompt never stalls in-flight
decodes for more than the one launch its chunk shares, and the weights
are streamed once an iteration.

RAGGED WORK PACKING: every model call is the ONE ragged step
(Executor.ragged_step_fn — flexflow_tpu.paged.attention): the tick
assembles WORK ITEMS (a decode row, a window-sized piece of a prefill
chunk, a drafted tree) into a (B, S) launch whose per-item descriptor
(pos, q_len, depths, anc) says which rows are live; items padded to the
launch shape carry q_len 0 and are skipped by the kernel, with their
writes redirected to the null page. Splitting a chunk into window
pieces is sound because every item's K/V rows scatter into the pool
BEFORE attention runs at each layer, so piece i+1 sees piece i's rows
as committed (kpos < pos) — the same mechanism that lets chunks span
ticks.

Decode flow per tick:
  1. admit queued requests into free slots while pages last (FIFO;
     preempted requests re-enter ahead of the queue); admission maps
     prefix-cache hits and allocates the remaining pages — no model run
  2. grow: decoding slots whose next write position crosses a page
     boundary allocate a page, preempting under pressure
  3. ONE launch an iteration. With a chunk to run: every mid-prefill
     slot's chunk pieces, then one q_len 1 item a decoding slot, in a
     window of the largest piece (a finishing chunk samples the first
     token). With none: the (slots, 1) decode step (idle slots carry
     q_len 0: no work, writes to the null page)
  4. sample the decoding slots' rows at their slot index, ON THE
     DEVICE, and advance what needs no token value (positions, prompt
     pages published, window pages released)
  5. take the picks of the launch BEFORE (append, publish generated
     pages, finish/free): the host waits for launch N only after launch
     N + 1 is dispatched, so the chip always has its next launch queued

LAUNCH AHEAD (docs/paged.md "Launch ahead"): the loop is a one-deep
pipeline. A decode row of launch N + 1 reads its token id from the
device vector of the slots' newest tokens (`_newest`, the step's
`feed`), which launch N's picks wrote; the host learns those values at
`_retire()`, a launch late. A request that stops on an EOS therefore has
one row in the launch after, whose pick is discarded (`late_stop_rows`)
and whose pages stay the request's until that launch is taken.
Whatever needs the host's truth of every token (preemption, defrag, a
drain-and-swap, stop(), speculation's drafting, a
hand-off) first calls `_retire(reason)`, the FENCE: the serial order is
this loop with the pipeline drained.
"""

from __future__ import annotations

import collections
import functools
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from flexflow_tpu import obs
from flexflow_tpu.paged.pool import EMPTY_HASH, PagePool
from flexflow_tpu.runtime.executor import (
    LAUNCH_DSA_STATS,
    LAUNCH_STATS,
    launch_columns,
)
from flexflow_tpu.serve_strategy import PREFILL_WINDOW_ROWS, ServeStrategy
from flexflow_tpu.serving import _GenerationServerBase, _GenRequest


def rows_at(tail, at):
    """Rows `at` (slots,) of `serving.probs_rows`' (slots, V) tail: the
    decode rows that rode a chunk's launch, each put at its SLOT's index
    (what the shared `_pick` draws by). ONE program a server: the gather
    costs a quarter of a second to compile, which a launch shape's own
    program must not (20 s of set-up over 73 shapes on the chip)."""
    import jax.numpy as jnp

    return jnp.take(tail, at, axis=0)


# a decode row's token id while the host has not seen it: `_launch` reads
# it from the device (`_newest`)
ON_DEVICE = -1


def set_newest(newest, picked, at):
    """The slots' newest tokens (slots,) with the picks (n,) put at the
    slots `at` (n,); an `at` past the last slot is dropped (a row of the
    pick that no live slot owns). ONE program a pick width (1, slots),
    whatever the launch's shape."""
    return newest.at[at].set(picked, mode="drop")  # fflint: cow-ok (a (slots,) vector of token ids, never a pool page)


class _Flight:
    """A launch whose picks the host has not taken: its number, the
    (slot, request) pairs that got a first token and a decode row's
    token in it, and the slots' newest tokens after its last pick."""

    __slots__ = ("seq", "firsts", "rows", "newest")

    def __init__(self, seq: int):
        self.seq = seq
        self.firsts: list = []
        self.rows: list = []
        self.newest = None


class PagedGenerationServer(_GenerationServerBase):
    """Continuous batching over the block-paged KV cache
    (serve_generation(..., paged=True)). Same public surface and sampling
    as the dense GenerationServer; HBM scales with the page pool instead
    of slots x max_len, so short sequences leave room to admit more
    concurrent work than the dense layout could hold, and shared prompt
    prefixes (system prompts, few-shot headers) are stored ONCE."""

    def __init__(self, ff, slots: int = 4, max_len: int = 512,
                 eos_id: Optional[int] = None, seed: int = 0,
                 page_size: int = 64, num_pages: Optional[int] = None,
                 preemption: bool = True, table_slack_tokens: int = 0,
                 prefix_cache: bool = True, prefill_chunk: int = 64,
                 request_record_limit: Optional[int] = None,
                 kv_dtype: str = "auto",
                 reqlog_capacity: Optional[int] = None,
                 slo=None, slo_dump_dir: Optional[str] = None,
                 kv_quant_canary: Optional[int] = None,
                 serve_strategy=None, defer_start: bool = False,
                 host_tier=None, num_pages_window: Optional[int] = None):
        import jax

        super().__init__(ff, slots, max_len, eos_id, seed,
                         request_record_limit=request_record_limit,
                         reqlog_capacity=reqlog_capacity,
                         slo=slo, slo_dump_dir=slo_dump_dir,
                         serve_strategy=serve_strategy,
                         defer_start=defer_start)
        self.page_size = int(page_size)
        # table_slack_tokens widens every page table beyond max_len —
        # speculative verify (flexflow_tpu.spec) writes its draft tree's
        # rows past the committed head, so the table must address up to
        # max_len + max_nodes rows even though pos never exceeds max_len
        self.table_slack = int(table_slack_tokens)
        self.max_pages_per_seq = -(
            -(self.max_len + self.table_slack) // self.page_size)
        if num_pages is None:
            # default pool matches the dense layout's capacity (+ null
            # page); size it DOWN to oversubscribe slots against HBM
            num_pages = self.slots * self.max_pages_per_seq + 1
        self.pool = PagePool(num_pages, self.page_size,
                             self.max_pages_per_seq)
        self.preemption = bool(preemption)
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk = max(1, int(prefill_chunk))
        # TWO CLASSES OF PAGES where the graph has sliding-window layers
        # (decided by the graph, Executor.page_classes): `pool` is the
        # full class, whose layers keep every row of a request, and
        # `pool_w` the window class, whose layers hold a request's last
        # `window` rows and the chunk being written: its pages behind the
        # window go back to its free list after every launch, so it is
        # sized slots x (window + prefill_chunk + one page), not slots x
        # max_len, and a request has a table in each class. A graph
        # without window layers has `pool_w` None and one table, as ever.
        self._window = int(ff.executor.window_rows())
        self.pool_w: Optional[PagePool] = None
        self._tables_w = None
        self.window_pages_released = 0
        self._released_at_launch = 0
        if self._window:
            self._w_slot_pages = min(
                -(-(self._window + self.prefill_chunk) // self.page_size)
                + 1, self.max_pages_per_seq)
            if num_pages_window is None:
                num_pages_window = self.slots * self._w_slot_pages + 1
            self.pool_w = PagePool(int(num_pages_window), self.page_size,
                                   self.max_pages_per_seq)
            self._tables_w = np.zeros(
                (self.slots, self.max_pages_per_seq), np.int32)
        elif num_pages_window is not None:
            raise ValueError(
                "num_pages_window sizes the window class of pages; this "
                "graph has no sliding-window layer")
        # packed prefill windows are capped at this many rows (the fp32
        # sublane tile): chunks larger than it split into pieces, so
        # launch shapes stay within a small (n_items, window<=8) family
        self._chunk_rows = PREFILL_WINDOW_ROWS
        ex = ff.executor
        if (jax.default_backend() == "tpu" and ex.mesh is not None
                and ex.mesh.devices.size > 1):
            # the pools are plain unsharded buffers and the ragged
            # pallas_call has no shard_map around it (unlike the flash
            # kernels' _sharded_flash): on a multi-chip mesh Mosaic
            # refuses to be partitioned and GSPMD would replicate the
            # whole pool on every chip. Sharded serving does not exist
            # yet — say so here, not in the first trace.
            raise NotImplementedError(
                f"paged serving runs on ONE chip: this model was compiled "
                f"on a {dict(zip(ex.mesh.axis_names, ex.mesh.devices.shape))}"
                " mesh. Compile the serving model with "
                "FFConfig(num_devices=1) — several one-chip replicas can "
                "sit behind disagg.PrefixAffinityRouter — or serve with "
                "paged=False.")
        # one ragged step serves decode AND chunked prefill (and tree
        # verify in the speculative subclass): K/V writes land straight
        # in pool pages, there is no dense staging cache
        self._step = ex.ragged_step_fn()
        # kv_dtype: "auto" pools at the model's dtype; "int8" stores
        # quantized pages with the per-(page, head) scale sidecar inside
        # the same caches dict (paged/quant.py), so copy_page/defrag
        # move scales with pages by construction;
        # "bf16"/"fp16"/"fp32" are plain storage casts without scales
        from flexflow_tpu.paged.quant import (
            is_quantized_dtype,
            resolve_kv_dtype,
        )

        self.kv_dtype = str(kv_dtype)
        pool_dt = resolve_kv_dtype(self.kv_dtype)  # validates the name
        self._quantized = (pool_dt is not None
                           and is_quantized_dtype(pool_dt))
        # FF_TPU_KV_QUANT_DEBUG=1 keeps a shadow fp32 cache and runs
        # every launch twice, exporting the running max abs output delta
        # as the kv_quant_error gauge (docs/observability.md).
        import os as _os

        self._kv_quant_debug = (
            self._quantized
            and _os.environ.get("FF_TPU_KV_QUANT_DEBUG") == "1")
        # STATE LAYERS (decided by the graph, Executor.state_layers): a
        # node that keeps a fixed-size state a SLOT beside the pages has
        # its leaves in the same `_caches` dict, indexed by slot; a launch
        # is told its items' slots and the layer does the rest on the
        # device (ops/kda_attention.py: a request's row 0 starts from
        # zero, so admission uploads nothing and a slot's reuse cannot
        # see its predecessor). What no state can follow,
        # `serve_generation` refuses. `_state_rows` / `_state_owner` are
        # the host's account of each slot's state for the invariant
        # catalog.
        self._state_keys = frozenset(ex.state_layers())
        # ... of which kinds: "kda" (delta rule), "ssd" (state space); a
        # launch's span counts its rows and pieces under each kind there
        self._state_kinds = ex.state_kinds()
        # ... and its launches come in FEW shapes: a step program with
        # state layers is the dearest to compile (each scan unrolled over
        # heads and rows), and one a (items, window) pair is 127 of them
        # at 8 slots and a 512-token chunk, 21 minutes of set-up on the
        # chip (PERF.md section 6, PR 44). A chunk's
        # launch therefore always has the full window, and is filled
        # with items without rows up to a multiple of `slots` items
        self._item_bucket = self.slots if self._state_keys else 1
        self._state_rows = np.zeros((self.slots,), np.int64)
        self._state_owner: List[Optional[int]] = [None] * self.slots
        self._state_launched: List[tuple] = []
        self.state_resets = 0
        # live items the state layers were handed, and those of ONE live
        # row among them (a state kernel's short form: `rows == 1`)
        self.state_items = 0
        self.state_items_one_row = 0
        self.state_resumes = 0
        self._caches = ex.init_paged_kv_cache(
            num_pages, self.page_size, dtype=pool_dt,
            num_pages_window=(self.pool_w.num_pages if self._window
                              else None), slots=self.slots)
        self.state_bytes_per_slot = sum(
            b.size * b.dtype.itemsize // self.slots
            for nk in self._state_keys for b in self._caches[nk].values())
        self._caches_ref = (ex.init_paged_kv_cache(
            num_pages, self.page_size, dtype=jax.numpy.float32)
            if self._kv_quant_debug else None)
        self._quant_err_dev = jax.numpy.float32(0.0)
        # kv_quant_canary=N: every Nth admitted request opens a SAMPLED
        # shadow window — _caches_ref becomes an fp32 snapshot of the
        # live pool (dequantized for int8, a cast otherwise) and every
        # launch replays against it until that request releases, feeding
        # the same kv_quant_error gauge at 1/N cost. The all-requests
        # FF_TPU_KV_QUANT_DEBUG=1 mode takes precedence over sampling.
        if kv_quant_canary is None:
            kv_quant_canary = int(
                _os.environ.get("FF_TPU_KV_QUANT_CANARY", "0") or 0)
        if kv_quant_canary < 0:
            raise ValueError(
                f"kv_quant_canary must be >= 0, got {kv_quant_canary}")
        self.kv_quant_canary = (0 if self._kv_quant_debug
                                else int(kv_quant_canary))
        self._canary_admits = 0
        self._canary_req: Optional[_GenRequest] = None
        self._c_canary = self.registry.counter(
            "kv_quant_canary_windows_total")
        if self._quantized:
            from flexflow_tpu.paged.quant import dequantize_pages

            @jax.jit
            def shadow_snapshot(caches):
                # the shadow starts COHERENT with the pool: what int8
                # storage says the cache holds, in fp32 — divergence
                # measured from here forward is pure quantization drift
                return {nk: {n: dequantize_pages(b, bufs[n + "_scale"])
                             for n, b in bufs.items()
                             if not n.endswith("_scale")}
                        for nk, bufs in caches.items()}
        else:
            @jax.jit
            def shadow_snapshot(caches):
                return jax.tree.map(
                    lambda b: b.astype(jax.numpy.float32), caches)
        self._shadow_snapshot = shadow_snapshot
        self._tables = np.zeros((self.slots, self.max_pages_per_seq),
                                np.int32)
        # device-resident descriptor mirrors (dirty-flagged, not re-
        # uploaded per tick): the page-table matrix changes only on
        # admission / growth / release / defrag, per-slot temps only on
        # admission / release, and the causal-chain depths/anc defaults
        # are pure functions of the launch shape
        self._tables_dev = None
        self._temps_dev = None
        self._chain_desc_cache = {}
        self._admit_order: List[int] = []  # live slots, oldest first
        self._requeue: List[_GenRequest] = []  # preempted, ahead of queue
        self._defrag_req = threading.Event()
        self.preemptions = 0
        self.defrags = 0
        self.peak_active = 0
        self.prefill_ticks = 0
        self._prefill_rr = 0  # rotating start slot for the chunk budget
        # iterations that held a chunk AND decoding slots, and those of
        # them whose decode rows rode the chunk's launch (all, unless a
        # subclass ticks them apart: the speculative server's verify)
        self.iterations_with_both = 0
        self.one_launch = 0
        self._rows_at = jax.jit(rows_at)
        # LAUNCH AHEAD: `_newest` is each slot's newest token as the
        # DEVICE knows it (born committed where a launch's outputs are,
        # as the pools), `_flight` the launches whose picks the host has
        # not taken, oldest first, `_synced` the newest launch the host
        # knows to be done. A launch is AHEAD when an earlier one was not
        # known to be done at its dispatch; one dispatched with nothing
        # before it is charged to the fence that drained the pipeline
        self._newest = jax.device_put(
            jax.numpy.zeros((self.slots,), jax.numpy.int32),
            ex.launch_placement())
        self._set_newest = jax.jit(set_newest)
        self._index_dev: dict = {}
        self._flight: "collections.deque[_Flight]" = collections.deque()
        self.launches = 0
        self.launches_ahead = 0
        # host-to-device transfers `_launch` made for its descriptors
        self.launch_uploads = 0
        # the ragged kernel's walks: items with work, and those of them
        # that rode the walk of the item before (`_walks`)
        self.kv_pieces = 0
        self.kv_pieces_shared = 0
        self._synced = 0
        self.fences: dict = {}
        self.launches_drained: dict = {}
        self._last_fence = "start"
        self.late_stop_rows = 0
        # idle-loop accounting (fftrace): ticks the loop slept because
        # nothing was live or admitted, and total seconds spent asleep
        self._c_idle = self.registry.counter("idle_ticks_total")
        self._c_idle_s = self.registry.counter("idle_wait_seconds_total")
        # ragged-launch accounting: how many launch rows each tick
        # shipped vs how many were padding (q_len 0 items / rows past an
        # item's q_len). The gauge holds the LAST tick's waste ratio;
        # the counters aggregate for the bench's end-to-end ratio.
        self._c_rows = self.registry.counter("launch_rows_total")
        self._c_pad = self.registry.counter("padded_rows_total")
        self._g_waste = self.registry.gauge("padding_waste_ratio")
        # one gate decision, surfaced: which attention path this server's
        # launches take (evaluated host-side at init — the gate only
        # depends on shapes/dtype/backend/env, all fixed for the server's
        # lifetime). A second server re-logs its own gate decisions.
        import os

        from flexflow_tpu.paged.attention import (
            head_pack,
            paged_attention_available,
            reset_rejection_log,
        )

        reset_rejection_log()
        attn_key, kbufs = next(kv for kv in self._caches.items()
                               if kv[0] not in self._state_keys)
        # a latent layer's pool has ONE entry a node, "c" (paged/latent.py)
        self._latent = "c" in kbufs
        kbuf = kbufs["c"] if self._latent else kbufs["k"]
        # pool rows are flat-lane (Hkv*D); the gate wants the head dim
        from flexflow_tpu.runtime.executor import node_key as _node_key

        attn = next(n.attrs for n in ex.topo if _node_key(n) == attn_key)
        interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
        # what the kernel derives its block of pages from, kept for the
        # launch_dispatch span's kv_blocks (tracing only)
        if self._latent:
            from flexflow_tpu.paged.latent import latent_attention_available

            self._block_geom = (kbuf.shape[2], kbuf.dtype, attn.num_heads)
            kernel_ok = latent_attention_available(
                self.page_size, interpret=interp, dtype=kbuf.dtype)
        else:
            # heads of 64 go through the kernel two kv heads a tile, so
            # twice the q heads fold into an entry's rows
            self._block_geom = (kbuf.shape[2], kbuf.dtype,
                                attn.num_heads // attn.num_kv
                                * head_pack(attn.kdim))
            kernel_ok = paged_attention_available(
                attn.kdim, self.page_size, interpret=interp,
                dtype=kbuf.dtype, kv_heads=attn.num_kv)
        self.kernel_variant = ("ragged_pallas" if kernel_ok
                               else "ragged_gather")
        # bytes a cached token takes in the pool, over every layer; a
        # sparse latent layer's pooled indexer keys ("kp", one row a
        # block of tokens on the same page) are counted apart
        def per_token(names):
            return sum(
                b.shape[1] * b.shape[2] * b.dtype.itemsize // self.page_size
                for nk, bufs in self._caches.items()
                if nk not in self._state_keys
                for n, b in bufs.items() if names(n))

        self.kv_bytes_per_token = per_token(
            lambda n: n != "kp" and not n.endswith("_scale"))
        self.index_bytes_per_token = per_token(lambda n: n == "kp")
        if self._window:
            # bytes of ONE page over the layers of each class, for the
            # launch spans' pool_bytes_* (tracing only)
            classes = ex.page_classes()
            per_class = [0, 0]
            for nk, bufs in self._caches.items():
                per_class[classes[nk]] += sum(
                    b.shape[1] * b.shape[2] * b.dtype.itemsize
                    for b in bufs.values())
            self._page_bytes_full, self._page_bytes_window = per_class
        # expert layers' launch counters (ops/expert_share.py STATS):
        # device arrays of launches not yet read, folded into host totals
        # where the host waits for the device anyway
        from flexflow_tpu.ffconst import OpType as _OpType

        self._has_moe = any(n.op_type == _OpType.EXPERT_SHARE
                            for n in ex.topo)
        # the sparse latent layers' attrs and the residual mixings a
        # launch runs, for `_sparse_counts`
        self._sparse = [n.attrs for n in ex.topo
                        if n.op_type == _OpType.LATENT_ATTENTION
                        and n.attrs.index_heads]
        self._hc_mixings = sum(
            n.op_type == _OpType.HYPER_CONNECTION and n.attrs.part == "pre"
            for n in ex.topo)
        self._sparse_totals: Dict[str, int] = {}
        self._moe_pending: List[tuple] = []
        self._moe_totals = np.zeros((4,), np.int64)
        self._g_kernel = self.registry.gauge("ragged_kernel_active")
        self._g_kernel.set(1.0 if self.kernel_variant == "ragged_pallas"
                           else 0.0)
        # kv_cache_dtype holds the pool's bits per K/V element (the
        # dtype NAME rides the metrics() dict); kv_quant_error the
        # running max abs output delta vs the fp32 shadow, 0 until the
        # debug flag samples it
        self._g_kv_dtype = self.registry.gauge("kv_cache_dtype")
        self._g_kv_dtype.set(kbuf.dtype.itemsize * 8)
        self._g_qerr = self.registry.gauge("kv_quant_error")
        self._g_qerr.set(0.0)
        # the canary is a WATCHDOG, not just a gauge: its alert
        # threshold is the "kv-canary-shadow-delta" band from the
        # numerics budget catalog (analysis/num_budgets.py — numcheck's
        # budget arm errors if the band is edited out from under us);
        # the running max crossing it counts a breach and logs once
        from flexflow_tpu.analysis.num_budgets import tolerance

        self.kv_quant_threshold = float(
            tolerance("kv-canary-shadow-delta"))
        self._quant_breached = False
        self._c_qbreach = self.registry.counter(
            "kv_quant_canary_breaches_total")
        # the DECLARED numerics plan this server serves (the paged
        # entries, at the pool's kv_dtype) — the same plan numcheck's
        # HLO arm audits against the lowered modules. The /v2 model
        # block + ff_dtype_plan_ok gauge report whether the live pool
        # still matches it, closing the audited-vs-served loop.
        self._dtype_plan = ex.dtype_plan(
            entries=["paged_decode", "verify"],
            kv_dtype=None if self.kv_dtype == "auto" else self.kv_dtype)
        self._g_plan_ok = self.registry.gauge("dtype_plan_ok")
        self._g_plan_ok.set(1.0 if self._dtype_plan_ok() else 0.0)
        self._weight_bytes = self._weights["bytes_served"]

        # the pool -> pool programs below CONSUME the pool they are given
        # (as the executor's launches do) and write it in place: every
        # caller rebinds what they return (docs/paged.md "Who owns the
        # pool")
        consuming = functools.partial(jax.jit, donate_argnums=(0,))

        @consuming
        def copy_page(caches, src, dst):
            # copy-on-write: clone one pool page (every cache buffer) so
            # a new owner can write past a shared partial prefix — the
            # scale-sidecar entries of a quantized pool are leaves of
            # the same dict, so the clone carries the donor's scales
            return jax.tree.map(lambda b: b.at[dst].set(b[src]), caches)

        self._copy_page = copy_page

        @consuming
        def reset_page_scales(caches, pages):
            # page lifecycle, not a row write: pages coming OFF the free
            # list get zero scales (grow-only within a lifetime starts
            # from zero; an empty page dequantizes to exact zeros).
            # LRU-revived pages never come through here — they keep
            # content, so they keep scales. `pages` is padded with the
            # null page 0, whose scale only ever covers garbage rows.
            return {
                nk: {n: (b.at[pages].set(0.0) if n.endswith("_scale")
                         else b)
                     for n, b in bufs.items()}
                for nk, bufs in caches.items()
            }

        self._scale_reset = reset_page_scales

        # host-memory KV tier (disagg/host_tier.py): evictions spill full
        # pages' payloads (scale sidecar included — it is a leaf of the
        # same caches dict) to host RAM instead of dropping them, and
        # lookups transparently fetch spilled prefixes back. Pass a
        # HostTier INSTANCE to share one tier between servers — that
        # shared tier is the prefill/decode KV-transfer channel
        # (disagg/workers.py) — or an int capacity for a private tier.
        @jax.jit
        def read_page(caches, page):
            # one compiled program for every page id: the index is data
            # (a reader: it consumes nothing and returns no pool)
            return jax.tree.map(lambda b: b[page], caches)

        @consuming
        def write_page(caches, page, payload):
            return jax.tree.map(
                lambda b, r: b.at[page].set(
                    jax.numpy.asarray(r).astype(b.dtype)), caches, payload)

        self._page_read = read_page
        self._page_write = write_page
        self.host_tier = None
        # an int capacity of 0 disables; an EMPTY HostTier instance must
        # not (it defines __len__, so plain truthiness would skip it)
        if host_tier is not None and host_tier != 0:
            from flexflow_tpu.disagg.host_tier import HostTier

            if self._kv_quant_debug:
                raise ValueError(
                    "host_tier and FF_TPU_KV_QUANT_DEBUG=1 are mutually "
                    "exclusive: the all-ticks fp32 shadow cannot observe "
                    "pages restored behind its back")
            self.host_tier = (host_tier if isinstance(host_tier, HostTier)
                              else HostTier(int(host_tier)))
            self.pool.attach_tier(self.host_tier, self._tier_read_page,
                                  self._tier_write_page)
        # spill/fetch counters ride the registry so they land on the
        # Prometheus endpoint as ff_kv_spill_pages_total /
        # ff_kv_fetch_pages_total; occupancy + fetch latency are gauges.
        # metrics() syncs them from the pool/tier truth at scrape time.
        self._c_spill = self.registry.counter("kv_spill_pages_total")
        self._c_fetch = self.registry.counter("kv_fetch_pages_total")
        self._g_tier_occ = self.registry.gauge("host_tier_occupancy_pages")
        self._g_tier_ratio = self.registry.gauge("host_tier_occupancy_ratio")
        self._g_tier_lat = self.registry.gauge("host_tier_fetch_latency_s")
        if self.serve_strategy is None:
            # derive the strategy from the ACTUAL constructor knobs so
            # fingerprint() always reflects what this server runs, even
            # when built without servesearch
            self.serve_strategy = self._derive_strategy()
        self._start()

    def shape_config(self) -> dict:
        """enumerate_catalog kwargs for this server's launch-shape space
        (analysis.shapecheck): the pool geometry plus every knob that
        changes which (B, W) ragged launches the scheduler can pack.
        The speculative subclass extends with its tree dimensions."""
        return {
            "slots": self.slots, "max_len": self.max_len, "paged": True,
            "page_size": self.page_size,
            "prefill_chunk": self.prefill_chunk,
            # num_pages is fixed at pool construction; the loop thread
            # never resizes the pool
            "num_pages": self.pool.num_pages,  # fflint: lock-ok (immutable)
            "kv_dtype": self.kv_dtype,
            "window_rows": self._chunk_rows,
            **({"num_pages_window": self.pool_w.num_pages}
               if self._window else {}),
            **({"item_bucket": self._item_bucket}
               if self._item_bucket > 1 else {}),
        }

    # -- capacity ---------------------------------------------------------

    def _peak_rows(self, prompt_len: int, max_new_tokens: int) -> int:
        """Cache rows a request touches at its deepest point (subclass
        hook: speculative verify adds its tree's scratch rows)."""
        return prompt_len + max_new_tokens

    def _check_capacity(self, prompt: np.ndarray, max_new_tokens: int):
        super()._check_capacity(prompt, max_new_tokens)
        need = self.pool.pages_for(self._peak_rows(len(prompt),
                                                   max_new_tokens))
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} pages at its longest "
                f"({len(prompt)}+{max_new_tokens} tokens, page_size="
                f"{self.page_size}) but the pool only holds "
                f"{self.pool.capacity}; raise num_pages")
        if self._window and min(need, self._w_slot_pages) > \
                self.pool_w.capacity:
            raise ValueError(
                f"request needs {min(need, self._w_slot_pages)} "
                f"window-class pages at its longest but that class only "
                f"holds {self.pool_w.capacity}; raise num_pages_window")

    def metrics(self) -> dict:  # fflint: lock-ok (relaxed metrics snapshot; int/float reads are atomic, staleness is fine for scraping)
        """Aggregate serving metrics + the per-request records of the
        last MAX_REQUEST_RECORDS completed requests (queue time, TTFT,
        prefill/decode tokens, pages — see _GenerationServerBase), plus
        pool occupancy/fragmentation and the prefix-cache counters (what
        the /v2/models/<name>/metrics endpoint scrapes)."""
        m = super().metrics()
        pool = self.pool
        if self._has_moe:
            from flexflow_tpu.ops.expert_share import STATS

            # the loop thread owns the pending list: the totals lag by
            # the launches it has not read yet (_fold_moe_stats)
            m.update(zip(STATS, (int(v) for v in self._moe_totals)))
        if self._sparse or self._hc_mixings:
            # what the sparse latent layers and the residual mixings had
            # to do, summed over the launches so far (`_sparse_counts`;
            # `selected_distinct` is counted on the device and lags like
            # the expert counters)
            m["sparse"] = dict(
                self._sparse_totals,
                index_bytes_per_token=self.index_bytes_per_token)
        m.update({
            "preemptions": self.preemptions,
            "defrags": self.defrags,
            "peak_active": self.peak_active,
            "pages_in_use": pool.pages_in_use,
            "free_pages": pool.free_pages,
            "cached_pages": pool.cached_pages,
            "pool_occupancy": pool.pages_in_use / pool.capacity,
            "fragmentation": pool.fragmentation(),
            "prefill_ticks": self.prefill_ticks,
            "launches": {
                "iterations_with_both": self.iterations_with_both,
                "one_launch": self.one_launch,
            },
            "launches_dispatched": self.launches,
            "launches_ahead": self.launches_ahead,
            "launch_uploads": self.launch_uploads,
            "kv_pieces": self.kv_pieces,
            "kv_walks": self.kv_pieces - self.kv_pieces_shared,
            "kv_pieces_shared": self.kv_pieces_shared,
            "launches_drained": dict(self.launches_drained),
            "fences": dict(self.fences),
            "late_stop_rows": self.late_stop_rows,
            "kernel_variant": self.kernel_variant,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "kv_cache_dtype": self._kv_pool_dtype_name(),
            "kv_quant_error": self._kv_quant_error(),
            "kv_quant_canary": {
                "every": self.kv_quant_canary,
                "debug_mode": self._kv_quant_debug,
                "windows": int(self._c_canary.value),
                "window_open": (self._canary_req is not None
                                or self._kv_quant_debug),
                "threshold": self.kv_quant_threshold,
                "breaches": int(self._c_qbreach.value),
            },
            "model": self._model_block(),
            "launch_rows": int(self._c_rows.value),
            "padded_rows": int(self._c_pad.value),
            "padding_waste_ratio": (
                self._c_pad.value / self._c_rows.value
                if self._c_rows.value else 0.0),
            "prefix_cache": {
                "enabled": self.prefix_cache,
                "hit_tokens": pool.hit_tokens,
                "miss_tokens": pool.lookup_tokens - pool.hit_tokens,
                "lookup_tokens": pool.lookup_tokens,
                "hits": pool.hits,
                "misses": pool.misses,
                "evictions": pool.evictions,
            },
        })
        if self._state_keys:
            m["state"] = {
                "kinds": list(self._state_kinds),
                "layers": len(self._state_keys),
                "bytes_per_slot": self.state_bytes_per_slot,
                "resets": self.state_resets,
                "resumes_by_recompute": self.state_resumes,
                "items": self.state_items,
                "items_one_row": self.state_items_one_row,
            }
        if self._window:
            m["page_classes"] = {
                "full": {"pages_in_use": pool.pages_in_use,
                         "free_pages": pool.free_pages},
                "window": {"pages_in_use": self.pool_w.pages_in_use,
                           "free_pages": self.pool_w.free_pages,
                           "window": self._window,
                           "released": self.window_pages_released},
            }
        # host-tier block + registry sync: counters follow THIS pool's
        # spill/fetch truth (a shared tier's totals aggregate producers;
        # per-server counters must not double-count), gauges follow the
        # tier. Synced at scrape time — both the JSON payload and the
        # Prometheus endpoint call metrics() first.
        self._c_spill.inc(pool.spilled_pages - self._c_spill.value)
        self._c_fetch.inc(pool.fetched_pages - self._c_fetch.value)
        tier = self.host_tier
        m["host_tier"] = {"enabled": tier is not None,
                          "spilled_pages": pool.spilled_pages,
                          "fetched_pages": pool.fetched_pages}
        if tier is not None:
            tm = tier.metrics()
            m["host_tier"].update(tm)
            self._g_tier_occ.set(tm["occupancy_pages"])
            self._g_tier_ratio.set(tm["occupancy_ratio"])
            self._g_tier_lat.set(tm["fetch_latency_s_avg"])
        return m

    def stop(self):
        super().stop()
        if self._thread is None or not self._thread.is_alive():
            self._fold_moe_stats()

    def _fold_moe_stats(self, keep: int = 0):
        """Read the expert layers' counters of all but the newest `keep`
        launches off the device into the host totals, and onto their
        launch_dispatch spans when traced (one list a counter, an entry a
        layer). Called from the loop thread every 120 launches, and from
        stop() for the rest, so the totals in metrics() lag by up to 128
        launches while the server runs."""
        from flexflow_tpu.ops.expert_share import STATS

        n = len(self._moe_pending) - keep
        if n <= 0:
            return
        done, self._moe_pending = (self._moe_pending[:n],
                                   self._moe_pending[n:])
        from flexflow_tpu.ops.latent_attention import DSA_STATS

        for attrs, stats, dsa in done:
            if stats is not None:
                vals = np.asarray(stats, np.int64)          # (layers, 4)
                self._moe_totals += vals.sum(axis=0)
                if attrs is not None:
                    attrs.update({name: vals[:, i].tolist()
                                  for i, name in enumerate(STATS)})
            if dsa is not None:
                vals = np.asarray(dsa, np.int64)    # (sparse layers, 1)
                for i, name in enumerate(DSA_STATS):
                    self._sparse_totals[name] = (
                        self._sparse_totals.get(name, 0)
                        + int(vals[:, i].sum()))
                    if attrs is not None:
                        attrs[name] = vals[:, i].tolist()

    def _kv_pool_dtype_name(self) -> str:
        """The pool's actual storage dtype name ("int8" for a quantized
        pool) — what the kv_cache_dtype gauge reports in bits."""
        bufs = next(b for nk, b in self._caches.items()
                    if nk not in self._state_keys)
        if self._latent:
            return str(bufs["c"].dtype)
        return str(bufs["k"].dtype)

    def _dtype_plan_ok(self) -> bool:
        """True while the live pool's storage dtype matches the declared
        plan's kv dtype — i.e. the server is serving the numerics it
        was audited against (numcheck HLO arm / --dtype-plan)."""
        from flexflow_tpu.runtime.executor import _HLO_DTYPE_NAMES

        pool = _HLO_DTYPE_NAMES.get(self._kv_pool_dtype_name())
        return pool == self._dtype_plan["paged_decode"]["kv"]

    def _model_block(self) -> dict:
        """The /v2 metrics "model" block: per-entry compute/accum/kv
        dtype names of the declared plan + whether the live pool still
        matches it (also the ff_dtype_plan_ok gauge)."""
        ok = self._dtype_plan_ok()
        self._g_plan_ok.set(1.0 if ok else 0.0)
        return {
            "dtype_plan": {e: {"compute": p["compute"],
                               "accum": p["accum"], "kv": p["kv"]}
                           for e, p in self._dtype_plan.items()},
            "dtype_plan_ok": ok,
        }

    # -- request log (obs.reqlog) ----------------------------------------

    def _prefix_chain(self, req: _GenRequest) -> tuple:
        """The pool's sha1 chain over the prompt's page-aligned blocks —
        entry i content-addresses the whole prefix through block i, so
        two records share a chain prefix iff their prompts shared those
        pages (the replay determinism tests diff these)."""
        return tuple(self.pool.chain_hashes(req.prompt))

    def _reqlog_kv_dtype(self) -> str:
        return self._kv_pool_dtype_name()

    def _reqlog_record(self, req: _GenRequest, m: dict,
                       done_t: float) -> dict:
        rec = super()._reqlog_record(req, m, done_t)
        rec["page_size"] = self.page_size
        return rec

    def _kv_quant_error(self) -> float:
        """Running max abs output delta vs the fp32 shadow cache (0.0
        unless FF_TPU_KV_QUANT_DEBUG=1 is sampling). Materialized from
        the device-resident running max only here, at scrape time, so
        the serving loop never pays a host sync for it."""
        err = float(self._quant_err_dev)
        self._g_qerr.set(err)
        if err > self.kv_quant_threshold and not self._quant_breached:
            # the running max only grows, so this fires once per
            # crossing — a breach is an alert, not a page of log spam
            self._quant_breached = True
            self._c_qbreach.inc()
            import logging

            logging.getLogger(__name__).warning(
                "kv_quant_error %.3g breached the "
                "kv-canary-shadow-delta budget %.3g "
                "(analysis/num_budgets.py): the quantized pool has "
                "drifted past its declared band vs the fp32 shadow",
                err, self.kv_quant_threshold)
        return err

    def request_defrag(self):
        """Ask the loop to compact the page pool between ticks (host
        bookkeeping + one device gather per cache buffer)."""
        self._defrag_req.set()

    # -- prefix-cache publication -----------------------------------------

    def _publish_prefix(self, req: _GenRequest, valid_rows: int):
        """Register every freshly FILLED page (all page_size rows hold
        committed K/V) under its token-prefix chain hash, so concurrent
        and future requests sharing the prefix map it instead of
        recomputing. Cheap no-op until a page boundary is crossed."""
        if not self.prefix_cache:
            return
        P = self.page_size
        target = min(valid_rows // P, len(req.pages))
        if req.hashed_blocks >= target:
            return
        seq = req.seq_tokens()
        chain = self.pool.chain_hashes(seq[:target * P])
        for b in range(req.hashed_blocks, target):
            self.pool.register_full(req.pages[b], chain[b])
        req.hashed_blocks = target

    def _publish_tail(self, req: _GenRequest):
        """On release/preemption: publish the remaining full pages and
        the partially filled tail page, so a resume (or an identical
        prompt) re-attaches these rows instead of recomputing them."""
        if not self.prefix_cache or not req.pages:
            return
        P = self.page_size
        valid = max(req.pos, req.prefill_pos)
        self._publish_prefix(req, valid)
        full = req.hashed_blocks
        tail = valid - full * P
        if tail > 0 and full < len(req.pages):
            seq = req.seq_tokens()
            chain = self.pool.chain_hashes(seq[:full * P])
            parent = chain[-1] if chain else EMPTY_HASH
            self.pool.register_partial(req.pages[full], parent,
                                       seq[full * P:valid])

    # -- slot lifecycle ---------------------------------------------------

    def _reset_prefill_state(self, req: _GenRequest):
        req.pos = 0
        req.prefill_pos = 0
        req.prefill_target = 0
        req.prefill_seq = None
        req.hashed_blocks = 0

    def _maybe_open_canary(self, req: _GenRequest):
        """Every `kv_quant_canary`-th successful admission opens a
        shadow window on that request: _caches_ref becomes an fp32
        snapshot of the CURRENT pool, so _launch's replay block measures
        divergence accrued from this admission forward. One window at a
        time; every launch is a tick, so the shadow observes each one."""
        if not self.kv_quant_canary or self._kv_quant_debug:
            return
        self._canary_admits += 1
        if (self._canary_admits % self.kv_quant_canary == 0
                and self._caches_ref is None):
            self._retire("canary")  # the window opens on the host's truth
            self._caches_ref = self._shadow_snapshot(self._caches)
            self._canary_req = req
            self._c_canary.inc()

    def _close_canary(self, req: _GenRequest):
        """Drop the shadow window when its request leaves (finish,
        cancellation, or preemption — a preempted request's replay
        would resume against a stale shadow)."""
        if self._canary_req is req:
            self._canary_req = None
            self._caches_ref = None

    def _release_slot(self, slot: int, req: _GenRequest,
                      completed: bool = False):
        self._free_pages(req)
        if self._active[slot] is req:   # not vacated at its last launch
            self._vacate(slot, req)
        super()._release_slot(slot, req, completed)

    def _free_pages(self, req: _GenRequest):
        """The request's side of its leaving (finished, preempted, carried
        over, handed off): its tail is published and its pages of both
        classes go back. A requeued request recomputes its window rows
        with the rest."""
        self._publish_tail(req)
        # free LEAF-first: a chain lookup stops at its first missing
        # block, so under pressure the LRU must reclaim tail pages before
        # the roots that every shared prefix runs through
        self.pool.free(list(reversed(req.pages)))
        req.pages = []
        if self._window:
            self.pool_w.free(list(req.window_pages.values()))
            req.window_pages = {}

    def _vacate(self, slot: int, req: _GenRequest):
        """The slot's side of a request's leaving: its table rows go to
        the null page and the next admission may take it. A request whose
        LAST token (by its count) is in flight vacates at that launch's
        dispatch, as the serial order frees the slot there, and keeps its
        PAGES until the token is taken (`_deliver` -> `_release_slot`):
        the launch in flight names them. A canary window on it closes
        here: its last launch has been replayed against the shadow."""
        if not self._kv_quant_debug:
            self._close_canary(req)
        self._tables[slot] = 0
        if self._window:
            self._tables_w[slot] = 0
        self._mark_tables_dirty()
        self._mark_temps_dirty()
        if slot in self._admit_order:
            self._admit_order.remove(slot)
        self._active[slot] = None
        self._state_owner[slot] = None    # its state is dropped with it

    def _evict(self, slot: int):
        """Preempt: free the victim's pages and requeue it (front); its
        future stays pending. With the prefix cache on, the freed pages
        stay content-addressed on the LRU dead list, so the resume
        re-attaches them and recomputes only whatever was evicted in
        between (req.seq_tokens() — the prompt itself is never mutated,
        so repeated preemptions cannot double-fold the prefix)."""
        req = self._active[slot]
        self._free_pages(req)
        self._reset_prefill_state(req)
        self._vacate(slot, req)
        req.preemptions += 1
        self.preemptions += 1
        self._requeue.insert(0, req)

    def _on_prefill_complete(self, slot: int):
        """Hook: runs inside _prefill_tick right after a request finishes
        its chunked prefill (tail published, first token sampled) and
        survived _finish_if_done. The monolithic server decodes in place;
        a disagg PrefillWorker (disagg/workers.py) overrides this to
        spill the request's pages into the shared host tier and hand the
        request to the decode worker instead."""

    # -- drain-and-swap (serving_autopilot) -------------------------------

    def _derive_strategy(self):
        """Reconstruct the ServeStrategy this server actually runs —
        called by the constructor when no explicit strategy was passed,
        so reqlog stamping and autopilot window segmentation work on
        hand-built servers too."""
        spec = getattr(self, "spec", None)
        dense_pages = self.slots * self.max_pages_per_seq
        frac = (1.0 if self.pool.num_pages >= dense_pages + 1
                else max((self.pool.num_pages - 1) / dense_pages, 1e-6))
        # a page (or chunk) wider than max_len behaves identically to
        # one clamped at max_len — clamp so the derived strategy passes
        # its own validate() and can round-trip through swap_to()
        return ServeStrategy(
            page_size=min(self.page_size, self.max_len),
            prefill_chunk=min(self.prefill_chunk, self.max_len),
            spec_width=(spec.width if spec is not None else 0),
            spec_depth=(spec.depth if spec is not None else 0),
            pool_fraction=round(frac, 6),
            kv_dtype=self.kv_dtype,
        )

    def _detach_active(self) -> List[_GenRequest]:
        """Carry-over side of detach_for_swap(): pull every live request
        off its slot WITHOUT touching its future. Pages are published to
        the prefix cache first (tail included) and then freed, so when
        the successor adopts this pool its re-admission re-attaches
        whatever content survives the LRU and recomputes only the rest.
        Not a preemption — futures stay pending, counters untouched.
        The loop took every pick in flight as it stopped (`_drain`)."""
        assert not self._flight, "detach with a launch in flight"
        carried: List[_GenRequest] = []
        for slot in list(self._admit_order):
            req = self._active[slot]
            if req is None:
                continue
            self._free_pages(req)
            self._reset_prefill_state(req)
            self._vacate(slot, req)
            carried.append(req)
        carried.extend(self._requeue)
        self._requeue.clear()
        return carried

    def absorb_requests(self, reqs: List[_GenRequest]):
        """Seed this not-yet-started server (defer_start=True) with the
        requests a predecessor carried out of detach_for_swap(). They
        land at the FRONT of the admission order, ahead of anything
        submitted to this server directly, so in-flight work resumes
        first after cutover."""
        if self._thread is not None:
            raise RuntimeError(
                "absorb_requests() requires a server whose loop has not "
                "started (construct with defer_start=True)")
        reqs = list(reqs)
        with self._lock:
            for req in reqs:    # the predecessor's seq means nothing here
                self._submitted += 1
                req.seq = self._submitted
        self._requeue[:0] = reqs

    def adopt_pool_from(self, old: "PagedGenerationServer") -> bool:
        """Take over the predecessor's PagePool and device caches when
        the pool geometry and storage dtype are identical, so content-
        addressed prefix pages survive the swap and carried requests
        re-attach instead of recomputing. Returns False on any mismatch
        (or when either side runs a debug shadow cache) and keeps the
        fresh pool — correct either way, just a colder start."""
        if self._thread is not None:
            raise RuntimeError(
                "adopt_pool_from() requires a server whose loop has not "
                "started (construct with defer_start=True)")
        # both loops are quiescent here: self raises above unless
        # defer_start, and the caller already joined the predecessor's
        # loop via detach_for_swap — nothing mutates either server
        # during the geometry comparison
        same = (not self._window and not old._window
                and self.page_size == old.page_size
                and self.pool.num_pages  # fflint: lock-ok (loops joined)
                == old.pool.num_pages
                and self.max_pages_per_seq == old.max_pages_per_seq
                and self._kv_pool_dtype_name() == old._kv_pool_dtype_name()
                and self._caches_ref is None  # fflint: lock-ok (joined)
                and old._caches_ref is None)
        if not same:
            return False
        self.pool = old.pool
        self._caches = old._caches
        return True

    # -- host-tier payload closures (disagg/host_tier.py) -------------------

    def _tier_read_page(self, page: int):
        """Snapshot one pool page to host: every cache buffer's row —
        the int8 scale-sidecar leaves live in the same dict, so scales
        travel with their page by construction. The payload keeps the
        caches dict's tree structure, so write restores it by tree_map."""
        import jax
        import jax.numpy as jnp

        return jax.device_get(
            self._page_read(self._caches, jnp.asarray(page, jnp.int32)))

    def _tier_write_page(self, page: int, payload):
        """Restore one spilled payload into a freshly allocated page
        (device_put rides the jitted scatter). A fetch rewrites pool
        content behind any open canary shadow, so the window closes —
        the probe aborts rather than report phantom divergence."""
        import jax.numpy as jnp

        t0 = time.monotonic()
        self._caches = self._page_write(
            self._caches, jnp.asarray(page, jnp.int32), payload)
        if self._caches_ref is not None and self._canary_req is not None:
            self._close_canary(self._canary_req)
        if self.host_tier is not None:
            self.host_tier.observe_fetch_seconds(time.monotonic() - t0)

    def adopt_request_pages(self, src: "PagedGenerationServer",  # fflint: lock-ok (quiescent receiver by contract — see docstring; no loop thread races these reads)
                            req: _GenRequest) -> int:
        """Per-request page adoption (the same-device KV-transfer path,
        generalizing adopt_pool_from's whole-pool swap): copy the FULL
        prefix pages `req`'s sequence has resident on `src` into this
        server's pool, registered under the same chain hashes and parked
        dead-cached, so this server's admission lookup re-attaches them.
        Direct device-to-device, for pools that share devices AND a
        quiescent receiver (this server's loop not yet started, or the
        call made from its own loop thread — _caches is loop-owned);
        the LIVE handoff path goes through a shared HostTier instead
        (disagg/workers.py), whose lock makes the transfer safe across
        worker threads. Returns pages adopted; a full pool or dtype
        mismatch adopts fewer — correct either way, the remainder
        recomputes."""
        if self._kv_pool_dtype_name() != src._kv_pool_dtype_name():
            return 0
        import jax.numpy as jnp

        adopted = 0
        seq = req.seq_tokens()
        for h in self.pool.chain_hashes(seq):
            if h in self.pool._full:  # fflint: pool-ok (resident already)
                continue
            page = src.pool._full.get(h)  # fflint: pool-ok (src quiesced at handoff)
            if page is None:
                break  # src chain broke; nothing deeper can be resident
            got = self.pool.alloc(1)
            if got is None:
                break
            self._caches = self._page_write(
                self._caches, jnp.asarray(got[0], jnp.int32),  # fflint: host-ok (one-time handoff copy, not a tick loop)
                src._tier_read_page(page))
            self.pool.register_full(got[0], h)
            self.pool.free(got)  # registered: parks on the LRU dead list
            adopted += 1
        return adopted

    def _reset_page_scales(self, pages: List[int]):
        """Zero the scale-sidecar entries of freshly ALLOCATED pages
        (no-op on unquantized pools). Called wherever pages come off the
        free list — admission's private pages and per-tick growth — so a
        page's grow-only scale lifetime starts at zero and a stale scale
        can never leak across owners. LRU revivals deliberately skip
        this: a revived page keeps its content, so it keeps its scale.
        The index vector pads with the null page to a fixed length so
        the jitted reset compiles once."""
        if not self._quantized or not pages:
            return
        import jax.numpy as jnp

        buf = np.zeros((self.max_pages_per_seq,), np.int32)
        buf[:len(pages)] = pages
        self._caches = self._scale_reset(self._caches, jnp.asarray(buf))

    def _admit(self, req: _GenRequest, slot: int) -> bool:
        """Map the longest cached prefix (shared full pages by refcount,
        copy-on-write clone of a matched partial tail), allocate private
        pages for the rest, and queue the uncached suffix for CHUNKED
        prefill. No model step runs here — prefill happens inside the
        decode loop, one budgeted chunk per tick."""
        import jax.numpy as jnp

        seq = req.seq_tokens()
        n = len(seq)
        P = self.page_size
        shared: List[int] = []
        cached = 0
        cow = None
        if self.prefix_cache:
            fetched0 = self.pool.fetched_pages
            shared, cached, cow = self.pool.lookup(seq)
            # attribute transparent host-tier fetches to THIS request
            # (reqlog `fetched_pages`; disagg handoff arrives this way)
            req.fetched_pages += self.pool.fetched_pages - fetched0
        # always recompute at least the LAST prompt token: its forward
        # pass produces the first sampled token's distribution (the
        # cache stores K/V, not logits)
        start = min(cached, n - 1)
        b0 = start // P            # first block this request writes into
        keep = shared[:b0]
        # a shared page at/after the write boundary must be cloned before
        # we write into it: the partial-tail donor, or — page-aligned
        # full-prompt hit — the last matched full page
        cow_src = cow if cow is not None else (
            shared[b0] if b0 < len(shared) else None)
        # start >= len(shared)*P - 1, so b0 >= len(shared) - 1: lookup
        # can never return full pages past the write boundary
        assert not shared[b0 + 1:], (shared, b0, cached, n)
        total = self.pool.pages_for(n)
        fresh = self.pool.alloc(total - b0)
        if fresh is None:
            # transient shortfall (LRU revival vs the conservative gate):
            # drop every cache hit and retry as a full recompute, and
            # roll the pool's hit counters back — these tokens end up
            # recomputed, not served from cache
            self.pool.free(keep + ([cow_src] if cow_src is not None
                                   else []))
            if cached > 0:
                self.pool.hit_tokens -= cached
                self.pool.hits -= 1
                self.pool.misses += 1
            shared, keep, cached, cow_src = [], [], 0, None
            start, b0 = 0, 0
            fresh = self.pool.alloc(total)
            if fresh is None:
                self._push_back(req)
                return False
        if cached > start:
            # full-prompt hit: the clamped last prompt token is
            # recomputed for its logits, not served — keep the pool's
            # hit_tokens in step with the per-request counters
            self.pool.hit_tokens -= cached - start
        pages = keep + fresh
        req.pages = pages
        req.peak_pages = max(req.peak_pages, len(pages))
        # fresh pages start a new scale lifetime BEFORE any COW clone,
        # so the clone's copied scale is not wiped
        self._reset_page_scales(fresh)
        self._tables[slot] = 0
        self._tables[slot, :len(pages)] = pages
        self._mark_tables_dirty()
        self._mark_temps_dirty()
        if cow_src is not None:
            self._caches = self._copy_page(
                self._caches, jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(pages[b0], jnp.int32))
            if self._caches_ref is not None:
                self._caches_ref = self._copy_page(
                    self._caches_ref, jnp.asarray(cow_src, jnp.int32),
                    jnp.asarray(pages[b0], jnp.int32))
            self.pool.free([cow_src])
        req.prefill_seq = seq
        req.prefill_pos = start
        req.prefill_target = n
        req.pos = 0
        req.hashed_blocks = min(b0, n // P)
        req.cached_prefill_tokens += start
        req.admit_t = time.monotonic()
        self._active[slot] = req
        self._admit_order.append(slot)
        if self._state_keys:
            # the slot's state is the request's from here and holds no
            # row: the launch that carries row 0 zeroes it on the device.
            # A preempted request comes back through here and recomputes
            # its state with its pages (prompt + emitted tokens)
            self._state_owner[slot] = req.seq
            self._state_rows[slot] = 0
            self.state_resets += 1
            self.state_resumes += req.preemptions > 0
        self._maybe_open_canary(req)
        return True

    def _pop_next(self) -> Optional[_GenRequest]:
        if self._requeue:
            return self._requeue.pop(0)
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _push_back(self, req: _GenRequest):
        self._requeue.insert(0, req)

    # -- the window class of pages ----------------------------------------

    def _window_peak(self, req: _GenRequest) -> int:
        """Window-class pages a request holds at its most: its whole
        length while that is short, else a window, a chunk and a page."""
        return min(self.pool.pages_for(self._peak_rows(len(req.prompt),
                                                       req.max_new)),
                   self._w_slot_pages)

    def _next_rows(self, req: _GenRequest) -> tuple:
        """(first row the request's next launch writes, how many it may
        write): a mid-prefill request's next chunk at its largest, a
        decoding one's next token."""
        if req.prefill_pos < req.prefill_target:
            return req.prefill_pos, min(self.prefill_chunk,
                                        req.prefill_target - req.prefill_pos)
        return req.pos, 1

    def _window_first_block(self, row: int) -> int:
        """The block of the oldest row a query at `row` sees in a window
        layer; every block before it is behind the window."""
        return max(row - self._window + 1, 0) // self.page_size

    def _window_target(self, req: _GenRequest) -> range:
        """The blocks a request's window table must map before its next
        launch: from its next row's window to that launch's last row."""
        row, n = self._next_rows(req)
        last = min((row + n - 1) // self.page_size,
                   self.max_pages_per_seq - 1)
        return range(self._window_first_block(row), last + 1)

    def _release_window_pages(self, slot: int, req: _GenRequest):
        """After a launch is dispatched: the window-class pages that lie
        wholly behind the window of the request's NEXT row go back to
        their class's free list, and their table entries to the null
        page. The launch just dispatched still reads them, through the
        table it was given, and runs before any launch that writes them
        again for another request."""
        if not self._window or not req.window_pages:
            return
        first = self._window_first_block(self._next_rows(req)[0])
        behind = [b for b in req.window_pages if b < first]
        if not behind:
            return
        self.pool_w.free([req.window_pages.pop(b) for b in behind])
        self._tables_w[slot, behind] = 0
        self.window_pages_released += len(behind)
        self._mark_tables_dirty()

    def _grow_window_pages(self, slot: int, req: _GenRequest) -> bool:
        """Map the blocks the request's next launch needs and does not
        hold yet. False when the window class cannot give them (the
        caller preempts, as for the full class)."""
        need = [b for b in self._window_target(req)
                if b not in req.window_pages]
        if not need:
            return True
        got = self.pool_w.alloc(len(need))
        if got is None:
            return False
        for b, page in zip(need, got):
            req.window_pages[b] = page
            self._tables_w[slot, b] = page
        self._mark_tables_dirty()
        return True

    def _window_debt(self) -> int:
        """Window-class pages the live requests may still take: each is
        owed up to its peak (admission must not hand those out)."""
        debt = 0
        for s in self._admit_order:
            req = self._active[s]
            if req is not None:
                debt += max(0, self._window_peak(req)
                            - len(req.window_pages))
        return debt

    def _check_invariants(self):
        """Debug hook for the loop's own thread (a test's wrapper of
        `_launch`; too hot for serving): both classes of pages against
        the invariant catalog, each with its owners, and the window
        class's tables against its requests (analysis/
        pool_invariants.py `check_window_class`)."""
        live = {s: self._active[s] for s in self._admit_order
                if self._active[s] is not None}
        # a request whose last token is in flight left its slot and still
        # owns its pages
        owners = {r.seq: r for r in live.values()}
        for rec in self._flight:
            owners.update((r.seq, r) for _s, r in rec.firsts + rec.rows)
        self.pool.check_invariants(
            {seq: r.pages for seq, r in owners.items()})
        from flexflow_tpu.analysis import pool_invariants

        if self._state_keys:
            violations = pool_invariants.check_slot_state(
                [(self._state_owner[s], int(self._state_rows[s]))
                 for s in range(self.slots)],
                {s: (r.seq, self._next_rows(r)[0]) for s, r in live.items()},
                self._state_launched, self._state_leaves())
            if violations:
                raise AssertionError(
                    "slot-state invariant violation(s):\n  "
                    + "\n  ".join(violations))
        if not self._window:
            return

        self.pool_w.check_invariants(
            {seq: list(r.window_pages.values())
             for seq, r in owners.items()})
        rows = {s: (r.window_pages, self._next_rows(r)[0])
                for s, r in live.items()}
        violations = pool_invariants.check_window_class(
            self._tables_w, rows, self._window, self.page_size)
        if violations:
            raise AssertionError(
                "window-class invariant violation(s):\n  "
                + "\n  ".join(violations))

    # -- page growth / preemption ----------------------------------------

    def _pages_target(self, req: _GenRequest) -> int:
        """Pages a live slot must hold BEFORE the next tick (subclass
        hook: speculative verify needs its whole tree's rows covered, not
        just the next write position). Mid-prefill slots already hold
        their prompt's pages (pos is 0 until prefill completes)."""
        return min(self.pool.pages_for(req.pos + 1), self.max_pages_per_seq)

    def _ensure_pages(self):
        """Before a tick, every live slot grows to its _pages_target
        (base: the page holding the next write position); pool pressure
        preempts the youngest OTHER live request (`preemption=False`
        requeues the starved request itself — a stall, never a wrong
        answer)."""
        for slot in list(self._admit_order):
            req = self._active[slot]
            if req is None or not self._packable(req):
                continue    # gone, or waiting for its last token only
            target = self._pages_target(req)
            while req is self._active[slot] and len(req.pages) < target:
                got = self.pool.alloc(1)
                if got is not None:
                    self._reset_page_scales(got)
                    req.pages.append(got[0])
                    req.peak_pages = max(req.peak_pages, len(req.pages))
                    self._tables[slot, len(req.pages) - 1] = got[0]
                    self._mark_tables_dirty()
                    continue
                self._preempt_for(slot)
            # the window class's blocks for the next launch, the same way
            while (self._window and req is self._active[slot]
                   and not self._grow_window_pages(slot, req)):
                self._preempt_for(slot)

    def _preempt_for(self, slot: int):
        """A class of pages cannot give `slot` what its next tick needs:
        evict the youngest OTHER request, or the starved one itself.
        Eviction requeues a request with the tokens the HOST has, and
        frees pages a launch in flight may name: first take what is in
        flight, and let the caller try the pool again (a request may
        have left)."""
        if self._retire("preempt"):
            return
        victims = [s for s in self._admit_order if s != slot]
        if self.preemption and victims:
            self._evict(victims[-1])  # youngest other request
        else:
            self._evict(slot)  # stall self until pages free up

    def _apply_defrag(self):
        # each class of pages is compacted by itself (one class unless
        # the graph has window layers) and each node's leaves are
        # gathered by their own class's permutation
        classes = self.ff.executor.page_classes() or {}
        pools = (self.pool, self.pool_w) if self._window else (self.pool,)
        perms, remaps = zip(*(pool.defrag() for pool in pools))
        # the gather covers every leaf of each node's dict — a quantized
        # pool's (num_pages, Hkv) scale sidecar permutes on the same
        # axis 0 as its pages, so scales follow pages through compaction.
        # A permutation cannot be gathered in place, so nothing is
        # donated here; each leaf is rebound as soon as its gather is
        # made, so the old leaf goes then and compaction holds one leaf
        # twice, never a second pool
        for caches in (self._caches, self._caches_ref):
            for nk, bufs in (caches or {}).items():
                if nk in self._state_keys:
                    continue        # indexed by slot, not by page
                perm = perms[classes.get(nk, 0)]
                for name in bufs:
                    bufs[name] = bufs[name][perm]
        # EVERY owner's table: the (slots, max_pages) matrix rewrite
        # covers every live slot (decoding and mid-prefill alike); shared
        # pages get the same new id in every owner's row because
        # old_to_new is one global map. The pool rewrote the hash index
        # and LRU inside defrag().
        self._tables = remaps[0][self._tables]
        if self._window:
            self._tables_w = remaps[1][self._tables_w]
        self._mark_tables_dirty()
        for s in self._admit_order:
            req = self._active[s]
            if req is not None:
                req.pages = [int(remaps[0][p]) for p in req.pages]
                req.window_pages = {b: int(remaps[1][p])
                                    for b, p in req.window_pages.items()}
        self.defrags += 1

    # -- scheduler loop ----------------------------------------------------

    def _admission_pages(self, req: _GenRequest) -> int:
        """Free pages required before admitting `req`: the prompt's rows
        PLUS the first decode tick's write row (an exact-page-multiple
        prompt would otherwise admit and immediately preempt for its
        first tick's page). Conservative: prefix-cache hits can only
        reduce what admission actually allocates. Subclass hook:
        speculative verify instead requires the whole first verify tree
        to fit."""
        return self.pool.pages_for(len(req.seq_tokens()) + 1)

    def _outstanding_growth(self) -> int:
        """Pages the already-live slots still need to reach their
        _pages_target — admission must not hand them out (a slot admitted
        this tick would otherwise trigger a first-tick preemption when
        _ensure_pages collects the debt)."""
        debt = 0
        for s in self._admit_order:
            req = self._active[s]
            if req is not None:
                debt += max(0, self._pages_target(req) - len(req.pages))
        return debt

    def _admit_pending(self) -> bool:
        """Admission: free slot + the request's page budget available
        (net of pages live slots are still owed), FIFO (a too-big head
        request blocks later ones — no starvation). Returns whether
        anything was admitted."""
        admitted = False
        for slot in range(self.slots):
            if self._active[slot] is not None:
                continue
            req = self._pop_next()
            if req is None:
                break
            if (self._admission_pages(req) + self._outstanding_growth()
                    > self.pool.free_pages):
                self._push_back(req)
                break
            if self._window and (self._window_peak(req) + self._window_debt()
                                 > self.pool_w.free_pages):
                self._push_back(req)    # the window class's budget
                break
            if not self._admit(req, slot):
                break
            admitted = True
        return admitted

    def _live(self) -> List[int]:
        return [s for s in range(self.slots) if self._active[s] is not None]

    def _mid_prefill(self, slot: int) -> bool:
        req = self._active[slot]
        return req is not None and req.prefill_pos < req.prefill_target

    # -- device-resident descriptor mirrors --------------------------------

    def _mark_tables_dirty(self):
        """Every `self._tables` write funnels through a call to this:
        the device mirror re-uploads on next use, never per tick."""
        self._tables_dev = None

    def _mark_temps_dirty(self):
        self._temps_dev = None

    def _tables_device(self):
        """The (slots, max_pages) page-table matrix on device, uploaded
        only when admission/growth/release/defrag dirtied it. A launch
        does not read it (its items' rows ride the launch's one upload,
        `_launch`); the speculative server's commit does."""
        import jax.numpy as jnp

        if self._tables_dev is None:
            # with window layers, a table a class: (2, slots, max_pages),
            # the full class's first (Executor.page_classes)
            # a COPY: the host writes `_tables` in place while a program
            # handed this upload is still in flight, and the CPU backend
            # aliases a numpy buffer it is given
            self._tables_dev = jnp.asarray(
                np.stack([self._tables, self._tables_w]) if self._window
                else self._tables.copy())
        return self._tables_dev

    def _temps_device(self):
        """Per-slot sampling temperatures on device (0.0 = greedy,
        also the empty-slot filler), uploaded only when slot occupancy
        changed."""
        import jax.numpy as jnp

        if self._temps_dev is None:
            self._temps_dev = jnp.asarray(np.array(
                [self._active[s].temperature if self._active[s] else 0.0
                 for s in range(self.slots)], np.float32))
        return self._temps_dev

    def _index_device(self, index):
        """A small int32 index vector on the device, uploaded once a
        VALUE: which slots a pick writes in `_newest` changes only when
        the slots' occupants do, so an iteration's pick adds no upload to
        its launch's one (about 0.25 ms each on the chip, PERF.md section
        6). Which entries of a launch READ `_newest` rides that launch's
        packed descriptor (`_launch`)."""
        import jax.numpy as jnp

        key = index.tobytes()
        hit = self._index_dev.get(key)
        if hit is None:
            if len(self._index_dev) >= 1024:
                self._index_dev.clear()
            hit = self._index_dev[key] = jnp.asarray(index)
        return hit

    def _chain_descriptor_device(self, B, window):
        """Cached device copies of the default causal-chain descriptor
        for a (B, window) launch: depths 0..window-1 and the lower-
        triangular ancestor relation, identical every tick of the same
        shape — only tree launches (speculative verify) override them."""
        key = (B, window)
        hit = self._chain_desc_cache.get(key)
        if hit is None:
            deps = np.tile(np.arange(window, dtype=np.int32), (B, 1))
            anc = np.tile(np.tril(np.ones((window, window), np.bool_)),
                          (B, 1, 1))
            hit = (self._upload(deps), self._upload(anc))
            self._chain_desc_cache[key] = hit
        return hit

    def _upload(self, array):
        """One host-to-device transfer of a launch's descriptors,
        counted where it is made (`launch_uploads`; the `launch_h2d`
        span's `uploads`)."""
        import jax.numpy as jnp

        self.launch_uploads += 1
        return jnp.asarray(array)

    def _launch(self, items, window, tr, ntr):
        """Run ONE ragged step over packed work items. Each item is
        (slot, pos, tokens, depths, anc): `tokens` the item's q_len <=
        window live token ids ([ON_DEVICE]: ONE row whose id is the
        slot's newest token on the device, which the host has not seen
        yet), depths/anc None for the causal-chain
        default (decode rows, chunk pieces) or the (window,) node depths
        and (window, window) ancestor relation of a drafted tree. Rows
        past an item's q_len are padding: the kernel skips them and the
        entry point redirects their K/V writes to the null page — an
        item NEVER needs its table row nulled, so mid-prefill and idle
        slots simply aren't packed. Returns (probs, padded, total) with
        probs (len(items), window, vocab); padding is also rolled into
        the launch counters and the per-tick waste gauge."""
        B = len(items)
        with obs.span("launch_build") as sp:
            if sp:
                sp.set(items=B, window=window)
            # ONE int32 array a launch carries everything the device is
            # told about its items (executor.launch_columns): `ids`,
            # `pos`, ... below are views into it. A FRESH array every
            # launch: launch N is in flight while the host builds N + 1,
            # and the CPU backend aliases a numpy buffer it is handed
            at, width = launch_columns(
                window, 2 if self._window else 1,
                table_cols=self.max_pages_per_seq)
            packed = np.zeros((B, width), np.int32)
            ids, pos, qls = (packed[:, at["ids"]], packed[:, at["pos"]],
                             packed[:, at["q_lens"]])
            slot_idx, feed = packed[:, at["slot"]], packed[:, at["feed"]]
            feed[:] = -1
            # the causal-chain default (decode rows, chunk pieces) is a
            # pure function of the launch shape — reuse its device copy
            # instead of re-uploading it every tick; only drafted trees
            # override it
            chain = all(d is None and a is None
                        for (_s, _p, _t, d, a) in items)
            if not chain:
                deps = np.tile(np.arange(window, dtype=np.int32), (B, 1))
                anc = np.tile(np.tril(np.ones((window, window), np.bool_)),
                              (B, 1, 1))
            for i, (slot, p, toks, d, a) in enumerate(items):
                ql = len(toks)
                if ql == 1 and toks[0] == ON_DEVICE:
                    feed[i] = slot
                else:
                    ids[i, :ql] = toks
                pos[i] = p
                qls[i] = ql
                slot_idx[i] = slot
                if d is not None:
                    deps[i] = d
                if a is not None:
                    anc[i] = a
            # the items' table ROWS, gathered here: a few kilobytes, and
            # the host's tables are the only copy a launch reads
            for cols_c, tables in zip(at["tables"],
                                      (self._tables, self._tables_w)):
                packed[:, cols_c] = tables[slot_idx]
            rode = self._walks(items, window, slot_idx, pos, qls, chain)
        with obs.span("launch_h2d") as sp:
            made = self.launch_uploads
            if chain:
                deps_d, anc_d = self._chain_descriptor_device(B, window)
            else:
                deps_d, anc_d = self._upload(deps), self._upload(anc)
            fed = {"packed": self._upload(packed),
                   "feed": (None, self._newest)}
            if sp:
                sp.set(launches=1, uploads=self.launch_uploads - made)
            sparse = (self._sparse_counts(slot_idx, pos, qls)
                      if self._sparse or self._hc_mixings else None)
            if self._state_keys:
                self._note_state_rows(slot_idx, pos, qls)
        total = B * window
        padded = total - int(qls.sum())
        # AHEAD: an earlier launch is not known to be done, so the chip
        # has this one queued before it runs dry
        ahead = self._synced < self.launches
        self.launches += 1
        self.launches_ahead += ahead
        if not ahead:
            self.launches_drained[self._last_fence] = (
                self.launches_drained.get(self._last_fence, 0) + 1)
        with obs.span("launch_dispatch") as sp:
            if sp:
                sp.set(launches=1, ahead=int(ahead))
                # what the ragged kernel has to walk for THIS launch,
                # counted at the launch: KV rows and pages of the items
                # with work, the blocks of block_pages pages it walks
                # them in, and causal (query, key) pairs
                from flexflow_tpu.paged.attention import ragged_block_pages

                q = qls[qls > 0].astype(np.int64)
                p0 = pos[qls > 0].astype(np.int64)
                P = self.page_size
                # a page counts ONCE a walk: the horizon of a run of
                # pieces is its last piece's (a kernel that reads a
                # chunk's prefix once must not read over 100 %)
                run = np.cumsum(~rode[qls > 0]) - 1
                horizon = np.zeros((int(run[-1]) + 1 if run.size else 0,),
                                   np.int64)
                np.maximum.at(horizon, run, p0 + q)
                pages = -(-horizon // P)
                lanes, pool_dt, rep = self._block_geom
                if self._latent:
                    from flexflow_tpu.paged.latent import latent_block_pages

                    ppb = latent_block_pages(P, self.max_pages_per_seq,
                                             lanes, pool_dt, rep * window)
                    # pages the launch has to read, each ONCE a slot: the
                    # pieces of one slot's chunk walk the same prefix, and
                    # a kernel that read it once must not read over 100 %
                    by_slot = {}
                    for s_, e_ in zip(slot_idx[qls > 0], p0 + q):
                        by_slot[int(s_)] = max(by_slot.get(int(s_), 0),
                                               int(e_))
                    sp.set(latent_pages=sum(-(-e_ // P)
                                            for e_ in by_slot.values()),
                           kv_bytes_per_token=self.kv_bytes_per_token)
                else:
                    ppb = ragged_block_pages(P, self.max_pages_per_seq,
                                             lanes, pool_dt, rep * window)
                sp.set(rows=total, padded_rows=padded,
                       kv_rows=int(horizon.sum()),
                       kv_pages=int(pages.sum()),
                       kv_blocks=int((-(-pages // ppb)).sum()),
                       block_pages=ppb,
                       kv_pieces=int(q.size), kv_walks=int(horizon.size),
                       kv_pieces_shared=int(q.size - horizon.size),
                       qk_pairs=int((q * p0 + q * (q + 1) // 2).sum()))
                if self._window:
                    sp.set(**self._window_counts(slot_idx[qls > 0], p0, q))
                alias = self._pool_alias.get((B, window))
                if alias is not None:
                    # whether this shape's pools are written where they
                    # lie, as its first call in warm-up showed
                    sp.set(pools_passed=alias[0], pools_in_place=alias[1])
                # the weight leaves this launch's program is handed
                sp.set(weight_bytes=self._weight_bytes)
                if self._state_keys:
                    # what the state layers have to do for THIS launch:
                    # slots whose state it touches, live rows and live
                    # items, named by the ops that are there
                    sp.set(state_slots=int(np.unique(slot_idx[qls > 0]).size),
                           slots=self.slots,
                           state_bytes_per_slot=self.state_bytes_per_slot,
                           kv_bytes_per_token=self.kv_bytes_per_token)
                    for kind in self._state_kinds:
                        sp.set(**{kind + "_rows": int(q.sum()),
                                  kind + "_pieces": int(q.size),
                                  kind + "_one_row": int((q == 1).sum())})
                if sparse is not None:
                    sp.set(index_bytes_per_token=self.index_bytes_per_token,
                           **sparse)
            probs, upd = self._step(
                tr, ntr, self._caches, None, None, None, deps_d, anc_d,
                **fed)
            stats = upd.pop(LAUNCH_STATS, None)
            dsa = upd.pop(LAUNCH_DSA_STATS, None)
            if stats is not None or dsa is not None:
                # a (layers, 4) device array: read later, in bulk and long
                # after the launch that made it has run (_fold_moe_stats:
                # a read of a ready array is a copy, a read a tick costs
                # the device 3.6 ms of waiting an iteration, PERF.md
                # section 6); a traced launch's span gets it then
                self._moe_pending.append((sp.attrs if sp else None, stats,
                                          dsa))
                if len(self._moe_pending) >= 128:
                    self._fold_moe_stats(keep=8)
        self._caches = upd
        if self._caches_ref is not None:
            # quant-error sampling (FF_TPU_KV_QUANT_DEBUG=1): the same
            # launch against the fp32 shadow cache; the running max abs
            # output delta over LIVE rows stays on device — metrics()
            # materializes it into the kv_quant_error gauge on scrape
            import jax.numpy as jnp

            probs_ref, upd_ref = self._step(
                tr, ntr, self._caches_ref, None, None, None, deps_d,
                anc_d, **fed)
            self._caches_ref = upd_ref
            live_rows = jnp.asarray(
                np.arange(window)[None, :] < qls[:, None])
            delta = jnp.max(jnp.abs(probs - probs_ref)
                            * live_rows[:, :, None])
            self._quant_err_dev = jnp.maximum(self._quant_err_dev, delta)
        self._c_rows.inc(total)
        self._c_pad.inc(padded)
        return probs, padded, total

    def _walks(self, items, window, slot_idx, pos, qls, chain):
        """(B,) bool: the items the host EXPECTS to ride the walk of the
        item before them in the ragged kernel: both have work, are causal
        chains of one slot, and this one starts where that one ends. A
        chunk's pieces after its first; never a decode row, a tree or a
        filler. What the kernel does it reads on the device from the
        table rows it is handed (paged/attention.py `ragged_runs`; one
        slot is one row); tests/test_paged.py holds the two equal on the
        scheduler's own launches. The latent kernel walks once a piece
        (paged/latent.py): nothing rides there."""
        from flexflow_tpu.paged.attention import ragged_shares_walks

        rode = np.zeros((len(items),), np.bool_)
        # a decode launch has one item a slot: nothing to look at
        same = slot_idx[1:] == slot_idx[:-1]
        if (not self._latent and ragged_shares_walks(len(items), window)
                and same.any()):
            ok = qls > 0
            if not chain:       # a drafted tree is a walk of its own
                ok &= np.fromiter(
                    (d is None and a is None
                     for (_s, _p, _t, d, a) in items), np.bool_, len(items))
            rode[1:] = (ok[1:] & ok[:-1] & same
                        & (pos[1:] == pos[:-1] + qls[:-1]))
        self.kv_pieces += int(np.count_nonzero(qls))
        self.kv_pieces_shared += int(np.count_nonzero(rode))
        return rode

    def _sparse_counts(self, slot_idx, pos, qls) -> dict:
        """What ONE sparse latent layer and the residual mixings have to
        do for a launch, from its items alone (the SPARSE form's work,
        whatever kernel does it): `index_blocks_scored`, (row, pooled
        key) pairs, a live row at position t scoring the t // pool whole
        blocks before its own; `selected_tokens`, the tokens the rows
        attend to (min(t // pool, blocks chosen) whole blocks and the
        t % pool + 1 tokens of the row's own) against `context_tokens`,
        the t + 1 a dense layer would; `index_pages`, the pooled-key
        pages read, once a slot; `hc_rows`, live rows times mixings. Kept
        as totals for metrics()["sparse"] too."""
        live = qls > 0
        t = (np.concatenate([np.arange(p, p + q) for p, q in
                             zip(pos[live], qls[live])])
             if live.any() else np.zeros((0,), np.int64)).astype(np.int64)
        out = {"hc_rows": int(t.size) * self._hc_mixings}
        if self._sparse:
            a = self._sparse[0]
            pool = a.index_pool
            horizon = {}
            for s_, e_ in zip(slot_idx[live], (pos + qls)[live]):
                horizon[int(s_)] = max(horizon.get(int(s_), 0), int(e_))
            out.update(
                index_blocks_scored=int((t // pool).sum()),
                selected_tokens=int((np.minimum(t // pool, a.index_blocks)
                                     * pool + t % pool + 1).sum()),
                context_tokens=int((t + 1).sum()),
                index_pages=sum(-(-e_ // self.page_size)
                                for e_ in horizon.values()))
        for k, v in out.items():
            self._sparse_totals[k] = self._sparse_totals.get(k, 0) + v
        return out

    def _state_leaves(self):
        """(name, held, declared) of every state leaf, each a (shape,
        dtype name) pair, for the `slot-state` invariant."""
        specs = self.ff.executor.paged_kv_cache_specs(
            self.pool.num_pages, self.page_size, slots=self.slots,
            num_pages_window=(self.pool_w.num_pages if self._window
                              else None))
        return [(f"{nk}.{name}",
                 (tuple(b.shape), b.dtype.name),
                 (tuple(specs[nk][name].shape), specs[nk][name].dtype.name))
                for nk in sorted(self._state_keys)
                for name, b in self._caches[nk].items()]

    def _note_state_rows(self, slot_idx, pos, qls):
        """The host's account of the states a launch continues (state
        graphs only): the rows each slot's state holds after it, and the
        launch's live items for `_check_invariants`."""
        self._state_launched = [
            (int(s), int(p), int(n))
            for s, p, n in zip(slot_idx, pos, qls) if n]
        for s, p, n in self._state_launched:
            self._state_rows[s] = p + n
        self.state_items += len(self._state_launched)
        self.state_items_one_row += int(np.count_nonzero(qls == 1))

    def _window_counts(self, slots, p0, q) -> dict:
        """What a traced launch has to read and score in a layer of each
        class (tracing only): live pages, each ONCE a slot however many
        pieces of its chunk walk them, and visible (query, key) pairs; in
        a window layer the pages from the window of the slot's first
        query to its last row, and min(position + 1, window) keys a
        query. Beside them what a window layer would have walked with no
        lower bound, the bytes both classes hold now over their layers,
        and what the full class's pages would take in every layer."""
        P, W = self.page_size, self._window
        first, horizon = {}, {}
        for s_, p_, q_ in zip(slots, p0, q):
            s_ = int(s_)
            first[s_] = min(first.get(s_, int(p_)), int(p_))
            horizon[s_] = max(horizon.get(s_, 0), int(p_ + q_))
        full = sum(-(-e // P) for e in horizon.values())
        win = sum(-(-horizon[s_] // P) - self._window_first_block(first[s_])
                  for s_ in horizon)
        pairs_w = 0
        for p_, q_ in zip(p0, q):
            seen = np.minimum(np.arange(int(p_), int(p_ + q_)) + 1, W)
            pairs_w += int(seen.sum())
        released = self.window_pages_released - self._released_at_launch
        self._released_at_launch = self.window_pages_released
        in_use, in_use_w = self.pool.pages_in_use, self.pool_w.pages_in_use
        return {
            "kv_pages_full": full, "kv_pages_window": win,
            "window_pages_walked_if_full": full,
            "qk_pairs_full": int((q * p0 + q * (q + 1) // 2).sum()),
            "qk_pairs_window": pairs_w,
            "window_pages_released": released,
            "pool_bytes_resident": (in_use * self._page_bytes_full
                                    + in_use_w * self._page_bytes_window),
            "pool_bytes_if_one_class": in_use * (
                self._page_bytes_full + self._page_bytes_window),
        }

    def _rider_rows(self, probs, at):
        """(slots, V): the rows of the decode items behind a chunk's
        pieces, each at its slot's index (`at`: where it lies among the
        launch's last `slots` entries)."""
        tail = self._probs_rows(probs, np.int32(0), np.int32(0),
                                self.slots)[1]
        return self._rows_at(tail, at)

    def _warm_riders(self, probs):
        import jax
        import jax.numpy as jnp

        rows = self._rider_rows(probs, np.zeros((self.slots,), np.int32))
        # a pick's way into `_newest`, at both widths (a first token, the
        # decode rows), from picks of the kind a tick makes; every slot
        # index is past the last, so nothing is written
        for n in (1, self.slots):  # fflint: host-ok (one-time warmup)
            picked = self._pick(rows[:n], jnp.zeros((n,), jnp.float32),
                                jax.random.key(0))
            self._set_newest(self._newest, picked, jnp.asarray(
                np.full((n,), self.slots, np.int32)))

    def _tick_prep(self) -> Optional[List[int]]:
        """Shared tick prologue (base and speculative loops): defrag if
        requested, admit, grow pages. Returns the slots with work to
        launch (decoding AND mid-prefill; not a slot whose request has
        its last token in flight), or None when this tick should be
        skipped (nothing to launch; sleeps briefly when nothing was
        admitted or in flight either). Admission runs beside a launch in
        flight: it touches free slots and free pages only."""
        with obs.span("tick_prep") as sp:
            obs.beacon()    # ties the spans' clock to a device trace's
            if self._defrag_req.is_set():
                self._defrag_req.clear()
                self._retire("defrag")      # no page moves under a launch
                with obs.span("defrag"):
                    self._apply_defrag()
            with obs.span("admit_pending"):
                admitted = self._admit_pending()
            live = self._live()
            self.peak_active = max(self.peak_active, len(live))
            if sp:
                sp.set(live=len(live),
                       pages_in_use=self.pool.pages_in_use,
                       admitted=admitted)
            if not any(self._packable(self._active[s]) for s in live):
                # nothing to launch: whoever is still in a slot waits for
                # a token in flight. Take it now (a finished request
                # leaves, and the next pass admits into its slot), never
                # sleep on it
                if not self._retire("idle") and not admitted:
                    # idle/busy-wait time is charged to its own span so a
                    # trace separates "waiting for work" from real prep
                    t0 = time.monotonic()
                    with obs.span("idle_wait"):
                        time.sleep(0.001)
                    self._c_idle.inc()
                    self._c_idle_s.inc(time.monotonic() - t0)
                return None
            self._ensure_pages()  # may preempt: recompute live after
            return [s for s in self._live()
                    if self._packable(self._active[s])] or None

    def _split_live(self, live):
        """(mid-prefill slots, decoding slots) for this tick."""
        pre = [s for s in live if self._mid_prefill(s)]
        dec = [s for s in live if not self._mid_prefill(s)]
        return pre, dec

    def _prefill_tick(self, slots, tr, ntr, dec=()):
        """Advance mid-prefill slots by chunks, at most `prefill_chunk`
        tokens ACROSS the tick (a shared Sarathi-style token budget —
        it bounds the tick's prefill FLOPs, protecting decode latency),
        writing K/V straight into their pool pages. The start slot
        rotates tick to tick so a long prompt cannot starve a later
        slot's prefill out of the budget indefinitely. The chunk
        finishing a prompt samples the request's first token from its
        own last-row logits — the same rng/_pick discipline as the
        dense server's admission prefill.

        Every slot's chunk is split into window-sized pieces and the
        whole tick rides ONE packed launch (piece i+1 sees piece i's
        rows as committed because K/V scatter precedes attention at
        each layer). The decoding slots `dec` ride the same launch, one
        q_len 1 item each after the chunk's pieces: the weights are
        streamed once for the iteration. Their rows are not sampled
        here; returns what `_decode_tick` picks them from (None without
        `dec`).

        After the dispatch the tick ADVANCES what needs no token value
        (prefill positions, the prompt's pages published, window pages
        released) and picks a finishing prompt's first token ON THE
        DEVICE; the host takes it a launch later (`_retire`). Without
        `dec` the tick also takes the launch before's picks here, as
        `_decode_tick` does otherwise."""
        waiting = any(not self._mid_prefill(s)
                      and self._packable(self._active[s])
                      for s in self._live())
        self.iterations_with_both += waiting
        self.one_launch += bool(dec)
        budget = self.prefill_chunk
        self.prefill_ticks += 1
        rot = self._prefill_rr % len(slots)
        self._prefill_rr += 1
        slots = slots[rot:] + slots[:rot]
        t0 = time.monotonic()
        sp = obs.span("prefill_tick").__enter__()
        # plan the tick's chunks first (budget in rotated order), then
        # launch, then publish/sample per slot in the SAME rotated
        # order, which fixes the rng split sequence of finishing chunks
        plan = []  # (slot, req, start, take)
        for s in slots:
            if budget <= 0:
                break
            req = self._active[s]
            take = min(budget, req.prefill_target - req.prefill_pos)
            plan.append((s, req, req.prefill_pos, take))
            budget -= take
        items = []
        ends = []  # index+row of each chunk's last piece in `items`
        # window = the tick's largest chunk, capped at _chunk_rows: small
        # chunks never pad past their own length and big chunks split
        # into pieces instead of rounding up to a power-of-two bucket
        W = min(self._chunk_rows, max(take for _, _, _, take in plan))
        if self._item_bucket > 1:
            W = min(self._chunk_rows, self.prefill_chunk)
        for s, req, start, take in plan:
            for off in range(0, take, W):
                piece = min(W, take - off)
                items.append((s, start + off,
                              req.prefill_seq[start + off:
                                              start + off + piece],
                              None, None))
            ends.append((len(items) - 1, (take - 1) % W))
        if self._item_bucket > 1:
            # a state graph's filler: no rows, the slot of the piece before
            short = -(len(items) + len(dec)) % self._item_bucket
            items += [(items[-1][0], 0, [], None, None)] * short
        # the decoding slots' items come LAST, so their rows are the end
        # of the launch's last `slots` entries: where each lies there, by
        # SLOT (the filler names the last one: real probabilities, unread)
        at = np.full((self.slots,), self.slots - 1, np.int32)
        for j, s in enumerate(dec):
            at[s] = self.slots - len(dec) + j
            items.append((s, self._active[s].pos, self._row_ids(s),
                          None, None))
        probs, padded, total = self._launch(items, W, tr, ntr)
        with obs.span("commit") as csp:
            # ADVANCE: what the launch did whatever its tokens are
            done = []
            for (s, req, start, take), (i, r) in zip(plan, ends):
                req.prefill_pos = start + take
                req.prefill_tokens += take
                self._publish_prefix(req, req.prefill_pos)
                if req.prefill_pos >= req.prefill_target:
                    # publish the PROMPT's partial tail now, before
                    # decode appends rows to the same page: the entry
                    # only names rows [0, tail) and those are immutable,
                    # so an identical or extending prompt can COW-clone
                    # this page while this request keeps decoding into
                    # it (no token is appended yet, so seq_tokens()
                    # still equals prefill_seq here)
                    req.pos = req.prefill_target
                    self._publish_tail(req)
                    done.append((s, req, i, r))
                self._release_window_pages(s, req)
            if csp:
                csp.set(finishing=len(done))
        for s, req, i, r in done:
            with obs.span("sample"):
                # the last real row, (1, V): one warmed program a launch
                # shape (serving.probs_rows)
                row = self._probs_rows(probs, np.int32(i), np.int32(r),
                                       self.slots)[0]
                self._keep_pick(self._pick_first_token(req, row),
                                np.array([s], np.int32), [(s, req)], True)
            if self._last_in_flight(req):
                self._vacate(s, req)
        if not dec:
            self._retire()
        chunked = self.prefill_chunk - budget
        self._g_waste.set(padded / total if total else 0.0)
        if sp:
            sp.set(slots=len(slots), chunk_tokens=chunked,
                   padded_rows=padded, total_rows=total,
                   rids=[req.seq for _s, req, _a, _t in plan],
                   takes=[take for _s, _r, _a, take in plan],
                   decode_waiting=int(waiting), decode_rode=int(bool(dec)))
        sp.__exit__(None, None, None)
        dt = time.monotonic() - t0
        self._h_prefill.observe(dt)
        led = obs.ledger()
        if led is not None:
            led.record("prefill", dt, batch=len(slots), chunk=chunked)
        return (probs, at) if dec else None

    def _decode_tick(self, live, tr, ntr, rode=None):
        """One single-token decode tick for the decoding slots: sample
        their rows at their slot index with the one shared `_pick`
        split, ON THE DEVICE, advance their positions, and take the
        picks of the launch BEFORE (`fetch`, the deliver `commit`): this
        tick's own are the next tick's. Their rows come from this tick's
        own (slots, 1) launch, or, in an iteration with a chunk, from
        the chunk's launch they rode (`rode`, what `_prefill_tick`
        returned): the tick then launches nothing. Mid-prefill slots
        count the tick as decode/prefill overlap. (Also dispatched by
        the speculative server when no live slot can use a tree —
        all-sampled ticks skip the tree-verify FLOPs.)"""
        import jax

        t0 = time.monotonic()
        sp = obs.span("decode_tick").__enter__()
        if sp:
            sp.set(live=len(live), pages_in_use=self.pool.pages_in_use,
                   rids=[self._active[s].seq for s in live])
        if rode is None:
            # one item per slot — q_len 1 for the decoding slots, 0 for
            # idle ones (no work, writes to the null page), so the launch
            # compiles once for (slots, 1) and probs stays slot-indexed
            dec = set(live)
            items = [(s, self._active[s].pos if s in dec else 0,
                      self._row_ids(s) if s in dec else [], None, None)
                     for s in range(self.slots)]
            probs, padded, total = self._launch(items, 1, tr, ntr)
            self._g_waste.set(padded / total if total else 0.0)
            if sp:
                sp.set(padded_rows=padded, total_rows=total)
        with obs.span("sample"):
            rows = (probs[:, -1, :] if rode is None
                    else self._rider_rows(*rode))
            self._rng, sub = jax.random.split(self._rng)
            picked = self._pick(rows, self._temps_device(), sub)
            at = np.full((self.slots,), self.slots, np.int32)
            at[live] = live
            self._keep_pick(picked, at,
                            [(s, self._active[s]) for s in live], False)
        with obs.span("commit"):
            # ADVANCE: every decoding slot wrote one row
            self._steps += 1
            for s in self._admit_order:
                if self._mid_prefill(s):
                    self._active[s].decode_overlap_ticks += 1
            for s in live:
                req = self._active[s]
                req.pos += 1
                self._release_window_pages(s, req)
                if self._last_in_flight(req):
                    self._vacate(s, req)
        self._retire()
        sp.__exit__(None, None, None)
        dt = time.monotonic() - t0
        self._h_tick.observe(dt)
        self._h_tokens.observe(len(live))
        led = obs.ledger()
        if led is not None:
            led.record("decode", dt, batch=len(live))

    # -- launch ahead: picks in flight, and the fence ----------------------

    def _packable(self, req: _GenRequest) -> bool:
        """Whether a slot's request has a row for the next launch: not
        once the host knows it stopped (an EOS, learnt a launch late: it
        stays in its slot until the row in flight is taken)."""
        return not self._finished(req)

    def _last_in_flight(self, req: _GenRequest) -> bool:
        """Whether the request's last token, by its count, is picked."""
        return len(req.tokens) + req.unseen >= req.max_new

    def _row_ids(self, slot: int):
        """A decode row's token ids for `_launch`: the host's newest
        token of the slot, or ON_DEVICE while that token is still in
        flight (the launch then reads it from `_newest`)."""
        if self._active[slot].unseen:
            return [ON_DEVICE]
        return [int(self._tokens[slot])]

    def _keep_pick(self, picked, at, owners, first: bool):
        """A pick of the launch just dispatched STAYS ON THE DEVICE: its
        tokens go into `_newest` at the slots `at`, where the next
        launch's decode rows read them, and the launch joins `_flight`
        with the (slot, request) pairs the host owes a token."""
        self._newest = self._set_newest(self._newest, picked,
                                        self._index_device(at))
        if not self._flight or self._flight[-1].seq != self.launches:
            self._flight.append(_Flight(self.launches))
        rec = self._flight[-1]
        (rec.firsts if first else rec.rows).extend(owners)
        rec.newest = self._newest
        for _s, req in owners:
            req.unseen += 1

    def _retire(self, reason: Optional[str] = None) -> bool:
        """Take the picks of launches in flight: fetch, then DELIVER what
        needs the values (append, generated pages published, EOS, first-
        token stamps, release, the futures). Without a `reason` it is the
        pipeline's own step, called right after a dispatch: every launch
        but the one just dispatched. With one it is the FENCE, for
        whatever needs the host's truth of every token: every launch,
        counted under `reason` when one was in flight. Returns whether
        anything was taken."""
        upto = self.launches if reason else self.launches - 1
        if not self._flight or self._flight[0].seq > upto:
            return False
        if reason:
            self.fences[reason] = self.fences.get(reason, 0) + 1
            self._last_fence = reason
        while self._flight and self._flight[0].seq <= upto:
            self._deliver(self._flight.popleft())
        return True

    def _deliver(self, rec: _Flight):
        with obs.span("fetch") as fsp:
            # the host's wait for the device: step, picks and the copy
            # out, of the launch BEFORE the one just dispatched
            toks = np.asarray(rec.newest)
            if fsp:
                fsp.set(bytes=int(toks.nbytes))
        self._synced = max(self._synced, rec.seq)
        with obs.span("commit") as csp:
            rids, late = [], 0
            for s, req in rec.firsts:
                req.unseen -= 1
                self._take_first_token(s, req, int(toks[s]))
                rids.append(req.seq)
                self._finish_if_done(s, req)
                if self._active[s] is req and not self._finished(req):
                    # disagg hook: a PrefillWorker hands the request off
                    # to its decode worker here instead of decoding it
                    self._on_prefill_complete(s)
            for s, req in rec.rows:
                req.unseen -= 1
                if self._finished(req):
                    # it stopped (EOS) in the launch before, which the
                    # host learnt after this row was dispatched: the row
                    # emits nothing and its K/V row is forgotten
                    late += 1
                    req.pos -= 1
                else:
                    tok = int(toks[s])
                    req.tokens.append(tok)
                    if self._active[s] is req:
                        self._tokens[s] = tok
                    rids.append(req.seq)
                    # rows whose K/V is written AND whose token the host
                    # has: all but the newest token's
                    self._publish_prefix(
                        req, len(req.prompt) + len(req.tokens) - 1)
                self._finish_if_done(s, req)
            self.late_stop_rows += late
            if csp:
                # the requests that gained a token HERE (token_gap reads
                # the span's end as the token's time)
                csp.set(rids=rids, late_stop_rows=late,
                        finished=sum(1 for _s, req in rec.firsts + rec.rows
                                     if req.future.done()))

    def _finish_if_done(self, slot: int, req: Optional[_GenRequest] = None):
        req = req or self._active[slot]
        if req is not None and req.unseen:
            # a launch in flight still names its pages (the row
            # dispatched before the host learnt of the stop): it leaves
            # when that launch is taken, not before
            return
        super()._finish_if_done(slot, req)

    def _host_tick(self, live, tr, ntr):
        """One iteration, ONE launch: the
        chunk's launch carries the decoding slots' rows whenever both
        kinds of work exist (a slot whose prompt finishes in it decodes
        from the next iteration on, as `dec` is settled before)."""
        pre, dec = self._split_live(live)
        if pre:
            rode = self._prefill_tick(pre, tr, ntr, dec)
            if dec:
                self._decode_tick(dec, tr, ntr, rode)
        else:
            self._decode_tick(dec, tr, ntr)

    def _loop_body(self, tr, ntr):
        while not self._stop.is_set():
            live = self._tick_prep()
            if live is None:
                continue
            self._host_tick(live, tr, ntr)

    def _drain(self):
        # what the launches in flight emitted is the callers': take it
        # before anything is cancelled or carried over to a successor
        try:
            self._retire("swap" if self._detaching else "stop")
        except Exception:  # the loop died in a launch: its picks with it
            for rec in self._flight:
                for _s, req in rec.firsts + rec.rows:
                    if not req.future.done():
                        req.future.cancel()
            self._flight.clear()
        super()._drain()
        for req in self._requeue:
            if not req.future.done():
                req.future.cancel()
        self._requeue.clear()
