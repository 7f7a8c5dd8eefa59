"""Executor: lowers a PCG (+ per-node ShardingViews) to jitted XLA programs.

This is the TPU-native replacement for the reference's entire task execution
pipeline (SURVEY.md §3.3-3.4): instead of per-op Legion IndexLauncher +
mapper + Realm data movement, the whole training iteration becomes ONE
`jax.jit`-compiled SPMD program over a device mesh:

  - forward: topo-order walk of the PCG, each node's registered lowering
    applied, node ShardingViews becoming `with_sharding_constraint`s (the
    parallel-op nodes are pure constraints);
  - backward: `jax.value_and_grad` over the forward (replacing hand-written
    backward tasks);
  - gradient sync: emitted automatically by GSPMD (psum over the data axis)
    — the reference's NCCL allreduce (optimizer_kernel.cu:88);
  - update: optimizer math fused into the same program;
  - Legion trace replay (flexflow_c.cc:1743) -> jit compile-once/replay.

Master weights stay fp32; lowerings cast to the activation dtype at use
sites, so bf16 compute with fp32 accumulation comes for free.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property, partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import LossType, MetricsType, OpType
from flexflow_tpu.ops.registry import LowerCtx, get_lowering
from flexflow_tpu.parallel.sharding import (
    ShardingView,
    batch_spec,
    prune_spec,
    spec_to_partition_spec,
)
from flexflow_tpu.pcg.graph import Graph, Node
from flexflow_tpu.runtime import initializer as init_mod
from flexflow_tpu.runtime.loss import compute_loss
from flexflow_tpu.runtime.metrics import compute_step_metrics
from flexflow_tpu.runtime.optimizer import Optimizer


def node_key(node: Node) -> str:
    return node.stable_key()


_WEIGHT_DTYPE_NAMES = {
    "fp32": "float32", "float32": "float32",
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "fp16": "float16", "float16": "float16",
    "fp8": "float8_e4m3fn", "float8_e4m3fn": "float8_e4m3fn",
}

# jnp dtype name -> the short HLO dtype name the lowered text prints
# (dtype_plan() speaks HLO names so numcheck diffs it against modules
# without a translation layer)
_HLO_DTYPE_NAMES = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2",
    "float64": "f64", "int8": "s8", "int32": "s32", "bool": "pred",
}


# key of a ragged step's per-node launch counters in its second result
LAUNCH_STATS = "__launch_stats__"
# and of its sparse latent layers' counters (ops/latent_attention.py
# DSA_STATS), one row a layer
LAUNCH_DSA_STATS = "__launch_dsa_stats__"


def launch_columns(window: int, classes: int = 1, *, table_cols=None,
                   width=None):
    """Where a launch's PACKED DESCRIPTOR keeps what: the one (B, width)
    int32 array a server uploads a launch and `ragged_step_fn`'s `packed`
    takes apart (docs/paged.md "The launch descriptor"). A row is an
    item: its `window` token ids, then `pos`, `q_lens`, its `slot` (the
    state a slot's index too) and the slot its first id is `feed` from
    or -1, one column each, then its row of the page table, once a class
    of pages (the full class's first). Sized by a table's columns (the
    host, which fills it) or by the array's width (the program, which
    slices it by the same map). Returns ({name: slice or column}, width)."""
    at = {"ids": slice(0, window), "pos": window, "q_lens": window + 1,
          "slot": window + 2, "feed": window + 3}
    lo = window + 4
    if table_cols is None:
        table_cols = (width - lo) // classes
    at["tables"] = [slice(lo + c * table_cols, lo + (c + 1) * table_cols)
                    for c in range(classes)]
    return at, lo + classes * table_cols


def _pool_buffers(caches) -> list:
    """The device buffer addresses of a pool's leaves, in tree order (a
    tuple a leaf: one address an addressable shard). What tells a pool
    written IN PLACE from one that was copied: warm_launch_shapes reads
    it before and after each shape's first call."""
    return [tuple(s.data.unsafe_buffer_pointer()
                  for s in leaf.addressable_shards)
            for leaf in jax.tree.leaves(caches)]


# the ops whose node owns a pool of the page cache
PAGED_ATTENTION_OPS = (OpType.MULTIHEAD_ATTENTION, OpType.RING_ATTENTION,
                       OpType.LATENT_ATTENTION)
# the ops whose node keeps a fixed-size STATE a slot beside the pages, and
# what the tracing calls the rows each kind takes of a launch
STATE_KINDS = {OpType.KDA_ATTENTION: "kda", OpType.MAMBA2: "ssd"}
STATE_OPS = tuple(STATE_KINDS)
# the ops of an expert layer, and what may stand on the way from the head's
# softmax back to its norm or between a feed-forward's linears
# (`Executor.node_groups`)
_EXPERT_OPS = (OpType.EXPERT_SHARE, OpType.EXPERTS, OpType.GROUP_BY,
               OpType.AGGREGATE, OpType.AGGREGATE_SPEC)
_ROUTER_OPS = (OpType.LINEAR, OpType.SOFTMAX, OpType.TOPK)
_NORM_OPS = (OpType.RMS_NORM, OpType.LAYER_NORM)
_HEAD_OPS = (OpType.SOFTMAX, OpType.LINEAR, OpType.TIED_HEAD,
             OpType.ELEMENT_UNARY, OpType.CAST) + _NORM_OPS
_ELEMENTWISE_OPS = (OpType.ELEMENT_UNARY, OpType.ELEMENT_BINARY)


def _cast_weight_leaf(arr, weight_dtype: str):
    """Storage cast for one initialized weight leaf
    (init_params(weight_dtype=...)): float names are a plain astype;
    "int8" snaps values to a symmetric per-leaf int8 grid and stores
    the result bf16 (paged.quant.quantize_leaf) because no executor
    matmul consumes raw int8 operands."""
    if weight_dtype == "int8":
        from flexflow_tpu.paged.quant import quantize_leaf

        return quantize_leaf(arr)
    name = _WEIGHT_DTYPE_NAMES.get(weight_dtype)
    if name is None:
        raise ValueError(
            f"unknown weight_dtype {weight_dtype!r}; expected one of "
            f"{sorted(set(_WEIGHT_DTYPE_NAMES))} or 'int8'")
    return arr.astype(jnp.dtype(name))


class _TracedStep:
    """Jitted step function wrapped in an fftrace span (obs.span) so
    train/eval steps land on the host trace next to the serving ticks,
    and compiled under a cache key that holds its named scopes
    (compile_cache.keyed_on_metadata). Everything else delegates to the
    underlying jitted callable: `.lower()` in particular, which
    lowered_modules()/hloaudit call on the object train_step() returns.
    With no recorder the span is the shared no-op singleton."""

    # __weakref__: jax.jit(step) weak-references the callable it wraps
    __slots__ = ("_fn", "_name", "__weakref__")

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kw):
        from flexflow_tpu import obs
        from flexflow_tpu.runtime.compile_cache import keyed_on_metadata

        with keyed_on_metadata(), obs.span(self._name):
            return self._fn(*args, **kw)

    def lower(self, *args, **kw):
        return self._fn.lower(*args, **kw)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class Executor:
    """Owns the lowered step functions for one compiled PCG."""

    def __init__(
        self,
        graph: Graph,
        mesh,
        *,
        loss_type: LossType,
        metrics: Sequence[MetricsType],
        optimizer: Optional[Optimizer],
        label_dtype=jnp.int32,
        seq_length: Optional[int] = None,
        donate: bool = True,
        remat: str = "attention",
        zero_sharded_opt: bool = False,
    ):
        self.graph = graph
        self.mesh = mesh
        self.loss_type = loss_type
        self.metrics = list(metrics)
        self.optimizer = optimizer
        self.label_dtype = label_dtype
        self.seq_length = seq_length
        self.donate = donate
        self.remat = remat
        # ZeRO-1: shard optimizer state over the data axis
        # (ParamSyncType.SHARDED — the reference's third sync mode beyond
        # PS/NCCL, config.h:55; here it cuts Adam state HBM by the data
        # degree and turns the grad psum into reduce-scatter + all-gather)
        self.zero_sharded_opt = zero_sharded_opt
        self.topo = graph.topo_order()
        self.input_nodes = [n for n in self.topo if n.op_type == OpType.INPUT]
        sinks = graph.sinks()
        if len(sinks) != 1:
            raise ValueError(f"PCG must have exactly one sink, got {sinks}")
        self.sink = sinks[0]
        self.last_op_is_softmax = self.sink.op_type == OpType.SOFTMAX
        # When the graph ends in Softmax and the loss is a cross-entropy,
        # train/eval skip the final softmax and fuse it into the loss as a
        # log-softmax (the reference's fused softmax-grad discipline,
        # loss_functions.cu:23). predict() still runs the real softmax.
        self.fuse_loss_softmax = self.last_op_is_softmax and loss_type in (
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            LossType.CATEGORICAL_CROSSENTROPY,
        )
        # AggregateSpec (speculative MoE) emits one row per (sample, k)
        # slot, so the loss must see each label k times — the reference's
        # repl_labels (model.cc:2875). Detected from the batch-dim ratio
        # sink/input when an AGGREGATE_SPEC node is in the graph.
        self.label_repeats = 1
        if any(n.op_type == OpType.AGGREGATE_SPEC for n in self.topo):
            try:
                in_b = self.input_nodes[0].outputs[0].dims[0].size
                out_b = self.sink.outputs[0].dims[0].size
                if in_b > 0 and out_b % in_b == 0 and out_b // in_b > 1:
                    self.label_repeats = out_b // in_b
            except (IndexError, AttributeError):
                pass
        self._train_step = None
        self._eval_step = None
        self._forward = None
        self._decode_fn = None
        self._ragged_step_fn = None
        self._node_groups = None
        self._paged_commit_fn = None
        # compile-event tracker (obs/compile_tracker.py): each decode-
        # path jit factory below hands its callable through wrap(), so
        # XLA cache misses surface as observable events the shapecheck
        # soundness gate diffs against the static launch-shape catalog
        from flexflow_tpu.obs.compile_tracker import CompileTracker

        self.compile_tracker = CompileTracker()
        # remat="hidden": recompute MLP hidden activations in backward
        # instead of saving them (SwiGLU gate/up/silu/mul diamonds and
        # Linear(+activation)->Linear expansion chains). At LLM shapes the
        # hidden tensors dominate saved-activation HBM (e.g. ~5.6 GB of the
        # ~0.9B Llama's batch-8 step) while costing ~2% extra FLOPs to
        # recompute — relieving the memory pressure that otherwise forces
        # XLA into auto-remat/spills next to full fp32 Adam state.
        self._remat_groups = (
            self._find_hidden_groups() if self.remat == "hidden" else {}
        )

    def _find_hidden_groups(self):
        """Detect rematerializable MLP-hidden groups. Returns
        {entry_guid: (nodes_in_topo_order, member_guids, out_key,
        ext_keys)} where out_key = (guid, idx) of the single group output
        consumed outside and ext_keys is the ordered tuple of external
        (src_guid, src_idx) inputs the checkpointed call consumes.

        Patterns (all ops stateless, single consumer each inside):
          A: MUL(UNARY(LINEAR_g(x)), LINEAR_u(x)) — SwiGLU diamond
          B: LINEAR(act!=NONE, expanding) -> LINEAR — fused-activation MLP
          C: LINEAR(expanding) -> UNARY -> LINEAR — unfused MLP
        """
        from flexflow_tpu.ffconst import ActiMode

        consumers: Dict[int, List] = {}
        for n in self.topo:
            for e in self.graph.out_edges(n):
                consumers.setdefault(n.guid, []).append(e)
        node_by_guid = {n.guid: n for n in self.topo}

        def single_consumer(guid):
            es = consumers.get(guid, [])
            return node_by_guid[es[0].dst] if len(es) == 1 else None

        def is_expanding(n):
            try:
                ins = self.graph.input_shapes(n)
                return n.outputs[0].dims[-1].size > ins[0].dims[-1].size
            except Exception:
                return False

        groups = {}
        claimed = set()
        topo_pos = {n.guid: i for i, n in enumerate(self.topo)}
        for m in self.topo:
            if m.guid in claimed:
                continue
            members = None
            if m.op_type == OpType.ELEMENT_BINARY and getattr(
                    m.attrs, "kind", None) in ("mul", "multiply"):
                ins = list(self.graph.in_edges(m))
                if len(ins) == 2:
                    a = node_by_guid[ins[0].src]
                    b = node_by_guid[ins[1].src]
                    # one side UNARY(LINEAR), other LINEAR, shared input
                    for s, u in ((a, b), (b, a)):
                        if (s.op_type == OpType.ELEMENT_UNARY
                                and u.op_type == OpType.LINEAR
                                and single_consumer(s.guid) is m
                                and single_consumer(u.guid) is m):
                            g_edges = list(self.graph.in_edges(s))
                            if not g_edges:
                                continue
                            g = node_by_guid[g_edges[0].src]
                            if (g.op_type == OpType.LINEAR
                                    and single_consumer(g.guid) is s
                                    and is_expanding(g) and is_expanding(u)):
                                gsrc = {(e.src, e.src_idx)
                                        for e in self.graph.in_edges(g)}
                                usrc = {(e.src, e.src_idx)
                                        for e in self.graph.in_edges(u)}
                                if gsrc == usrc:
                                    members = [g, u, s, m]
                            break
            elif (m.op_type == OpType.LINEAR and is_expanding(m)
                  and getattr(m.attrs, "activation", ActiMode.NONE)
                  is not ActiMode.NONE):
                nxt = single_consumer(m.guid)
                if nxt is not None and nxt.op_type == OpType.LINEAR:
                    members = [m]
            elif m.op_type == OpType.LINEAR and is_expanding(m):
                nxt = single_consumer(m.guid)
                if nxt is not None and nxt.op_type == OpType.ELEMENT_UNARY:
                    nxt2 = single_consumer(nxt.guid)
                    if (nxt2 is not None and nxt2.op_type == OpType.LINEAR
                            and single_consumer(m.guid) is nxt):
                        members = [m, nxt]
            if members:
                # swallow the trailing contraction Linear when it is the
                # sole consumer: the group then outputs the small
                # model-dim tensor and the big hidden input to the
                # contraction's wgrad is recomputed, not saved
                tail = single_consumer(members[-1].guid)
                if (tail is not None and tail.op_type == OpType.LINEAR
                        and not is_expanding(tail)
                        and tail.guid not in claimed):
                    members.append(tail)
            if not members or any(n.guid in claimed for n in members):
                continue
            members.sort(key=lambda n: topo_pos[n.guid])
            member_set = {n.guid for n in members}
            # external inputs, in first-use order; all must be computed
            # before the entry node is reached in the topo walk
            ext = []
            ok = True
            for gn in members:
                for e in self.graph.in_edges(gn):
                    if e.src in member_set:
                        continue
                    if (e.src, e.src_idx) not in ext:
                        if topo_pos[e.src] > topo_pos[members[0].guid]:
                            ok = False
                        ext.append((e.src, e.src_idx))
            if not ok:
                continue
            out = members[-1]
            groups[members[0].guid] = (
                members, member_set, (out.guid, 0), tuple(ext)
            )
            claimed.update(n.guid for n in members)
        self._remat_member_of = {
            g: entry for entry, (mem, _, _, _) in groups.items()
            for g in (n.guid for n in mem)
        }
        return groups

    # ------------------------------------------------------------------
    # parameter creation

    def weight_specs(self) -> Dict[str, Dict[str, Any]]:
        """(node_key -> weight name -> WeightSpec) for all ops with weights."""
        out = {}
        for n in self.topo:
            if n.attrs is None or n.op_type == OpType.INPUT:
                continue
            ins = self.graph.input_shapes(n)
            ws = n.attrs.weights(*ins)
            if ws:
                out[node_key(n)] = ws
        return out

    def param_shardings(self):
        """NamedSharding pytrees for (trainable, nontrainable) params from
        the nodes' ShardingViews (replicated when unspecified)."""
        from jax.sharding import NamedSharding, PartitionSpec

        tr, ntr = {}, {}
        for n in self.topo:
            key = node_key(n)
            if n.attrs is None or n.op_type == OpType.INPUT:
                continue
            ws = n.attrs.weights(*self.graph.input_shapes(n))
            if not ws:
                continue
            view: Optional[ShardingView] = n.sharding
            for name, spec_decl in ws.items():
                pspec = PartitionSpec()
                if view is not None and name in view.weight_specs:
                    spec = prune_spec(
                        view.weight_specs[name], spec_decl.shape.dims, self.mesh
                    )
                    pspec = spec_to_partition_spec(spec)
                sh = NamedSharding(self.mesh, pspec)
                (tr if spec_decl.trainable else ntr).setdefault(key, {})[name] = sh
        return tr, ntr

    def init_params(self, rng, overrides: Optional[Dict] = None,
                    weight_dtype: Optional[str] = None):
        """Initialize (trainable, nontrainable) param pytrees, resharding
        each weight to its strategy NamedSharding as it is drawn. The
        draws run UNPARTITIONED on purpose: a sharded model must
        train/decode from the SAME weights as the unsharded reference
        at the same seed whatever the RNG's partitioning mode (seed
        failure: test_decode_sp_pp token identity; the four-chip TP
        loss is compared with the one-chip loss on that footing).
        Values first, layout second — leaf by leaf, so the whole model
        never resides unsharded on one device.
        `overrides` maps node_key -> weight name -> Initializer (the layer
        methods' kernel_initializer arguments).

        `weight_dtype` stores every leaf at a dtype AFTER the draw, for a
        model that never trains: a float name ("bf16"/"fp16"/"fp8")
        casts (use sites re-cast to compute dtype), "int8" snaps values
        to a per-leaf int8 grid, stored bf16 (paged.quant.quantize_leaf:
        no executor matmul consumes raw int8). None keeps fp32 masters,
        the training default; a server of such a model launches with
        FFModel.serving_params(), the masters narrowed once beside them."""
        specs = self.weight_specs()
        overrides = overrides or {}

        keys = {}
        i = 0
        for nk, ws in sorted(specs.items()):
            for wn in sorted(ws):
                keys[(nk, wn)] = i
                i += 1

        # one weight at a time: the unsharded draw lives only until its
        # device_put reshards it, so peak memory is the sharded tree plus
        # ONE full leaf — never the whole model on one device
        tr_sh, ntr_sh = self.param_shardings()
        tr, ntr = {}, {}
        for nk, ws in specs.items():
            for wn, spec in ws.items():
                ini = overrides.get(nk, {}).get(wn) or init_mod.resolve(
                    spec.initializer
                )
                sub = jax.random.fold_in(rng, keys[(nk, wn)])
                # master weights in fp32 (bf16 cast happens at use site)
                dtype = spec.shape.dtype.jnp_dtype
                if dtype == jnp.bfloat16 or dtype == jnp.float16:
                    dtype = jnp.float32
                arr = ini(sub, spec.shape.dims, dtype)
                if weight_dtype is not None:
                    arr = _cast_weight_leaf(arr, weight_dtype)
                sh = (tr_sh if spec.trainable else ntr_sh)[nk][wn]
                d = tr if spec.trainable else ntr
                d.setdefault(nk, {})[wn] = jax.device_put(arr, sh)
        return tr, ntr

    # ------------------------------------------------------------------
    # optimizer state (ZeRO-1 sharding)

    def _data_degree(self) -> int:
        """Full data-group degree: data x data_sub when the submesh split
        is active (ZeRO state shards over the whole group)."""
        if self.mesh is None:
            return 1
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return sizes.get("data", 1) * sizes.get("data_sub", 1)

    def opt_state_shardings(self, params):
        """Per-leaf NamedShardings for optimizer state trees that mirror
        `params` (Adam m/v, SGD momentum): each leaf additionally shards its
        largest data-divisible free dim over `data`. Scalars (step counters)
        and non-mirroring leaves stay replicated. Returns a function usable
        with jax.tree.map over a state tree."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self.mesh
        ddeg = self._data_degree()
        tr_sh, _ = self.param_shardings()
        repl = NamedSharding(mesh, PartitionSpec())

        # param leaf path (nk, wn) -> the param's PartitionSpec
        def param_spec(nk, wn):
            sh = tr_sh.get(nk, {}).get(wn)
            return sh.spec if sh is not None else PartitionSpec()

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        data_group = tuple(a for a in ("data", "data_sub")
                           if sizes.get(a, 1) > 1)

        def leaf_sharding(nk, wn, shape):
            if not self.zero_sharded_opt or ddeg <= 1 or not shape:
                return NamedSharding(mesh, param_spec(nk, wn))
            spec = list(param_spec(nk, wn))
            spec += [None] * (len(shape) - len(spec))
            # pick the largest dim not already sharded and divisible by
            # the full data group (data x data_sub under the submesh split)
            best, best_size = -1, 0
            for i, (entry, size) in enumerate(zip(spec, shape)):
                if entry is None and size % ddeg == 0 and size > best_size:
                    best, best_size = i, size
            if best >= 0 and data_group:
                spec[best] = (data_group if len(data_group) > 1
                              else data_group[0])
            return NamedSharding(mesh, PartitionSpec(*spec))

        def shardings_like(params_tree):
            return {
                nk: {
                    wn: leaf_sharding(nk, wn, jnp.shape(arr))
                    for wn, arr in ws.items()
                }
                for nk, ws in params_tree.items()
            }

        return shardings_like, repl

    def init_opt_state(self, optimizer, params):
        """Build optimizer state with ZeRO shardings applied (replicated
        when zero_sharded_opt is off)."""
        if self.mesh is None:
            return optimizer.init_state(params)
        shardings_like, repl = self.opt_state_shardings(params)
        state_shape = jax.eval_shape(optimizer.init_state, params)
        ptree = jax.tree.structure(params)

        def tree_shardings(sub):
            # state entries mirroring the params tree get ZeRO shardings
            if jax.tree.structure(sub) == ptree:
                return shardings_like(sub)
            return jax.tree.map(lambda _: repl, sub)

        out_sh = {k: tree_shardings(v) for k, v in state_shape.items()}
        self._opt_shardings = out_sh
        return jax.jit(optimizer.init_state, out_shardings=out_sh)(params)

    # ------------------------------------------------------------------
    # forward

    def _apply_view(self, node: Node, vals: List):
        view: Optional[ShardingView] = node.sharding
        if view is None or self.mesh is None:
            return vals
        from jax.sharding import NamedSharding

        out = []
        for i, v in enumerate(vals):
            spec = view.output_spec(i)
            if spec is None:
                out.append(v)
            else:
                ps = spec_to_partition_spec(prune_spec(spec, v.shape, self.mesh))
                out.append(jax.lax.with_sharding_constraint(v, NamedSharding(self.mesh, ps)))
        return out

    def run_forward(self, trainable, nontrainable, inputs: Sequence, *,
                    training: bool, rng, skip_sink_softmax: bool = False,
                    kv_caches=None, cache_position=None, cache_out=None,
                    page_tables=None, ragged=None, state_slots=None):
        """Topo-order lowering. Returns (sink output, state_updates, aux_loss).
        With `skip_sink_softmax` the final Softmax node passes its input
        (raw logits) through — used when the loss fuses the softmax.
        `kv_caches`/`cache_position` switch attention nodes into
        autoregressive cache mode; updated buffers land in `cache_out`.
        `page_tables` additionally switches the cache mode to PAGED:
        kv_caches are global page pools and each slot's rows are reached
        through its (slots, max_pages) int32 table row, and `ragged`
        carries the per-slot work descriptor (q_lens, depths, anc) that
        says which of the step's S query rows are live and what they may
        see — decode, chunked prefill and speculative tree verify are
        all this one step (flexflow_tpu.paged.attention). With
        page_tables set and `ragged` None, the causal-chain default
        (every row live, tril visibility) is used."""
        values: Dict[Tuple[int, int], Any] = {}
        if len(inputs) != len(self.input_nodes):
            raise ValueError(
                f"expected {len(self.input_nodes)} inputs, got {len(inputs)}"
            )
        for n, x in zip(self.input_nodes, inputs):
            values[(n.guid, 0)] = x
        state_updates: Dict[str, Dict[str, Any]] = {}
        aux_loss = 0.0
        if page_tables is not None and ragged is None:
            # causal-chain default: reproduces the pre-ragged decode /
            # chunk semantics (every row live, kpos <= qpos) for callers
            # that don't pack their own descriptor
            from flexflow_tpu.paged.attention import chain_descriptor

            ragged = chain_descriptor(inputs[0].shape[0],
                                      inputs[0].shape[1])
        ragged_q_lens, ragged_depths, ragged_anc = (
            ragged if ragged is not None else (None, None, None))
        # a graph with window layers is served from two classes of pages
        # and its launches carry a (2, B, max_pages) table, the full
        # class's rows first (`page_classes`); any other graph's table is
        # the (B, max_pages) matrix it always was
        classes = (self.page_classes()
                   if page_tables is not None and page_tables.ndim == 3
                   else None)
        remat_groups = self._remat_groups if training else {}
        # a PAGED step says besides what each node is for: the node's own
        # scope inside its group's (`node_groups`; obs/scopes.py
        # `classify_serving`). Every other step's stacks are what they were
        from flexflow_tpu.obs import scopes

        groups = self.node_groups() if page_tables is not None else None

        def scope_of(key):
            return jax.named_scope(
                key if groups is None else f"{groups[key]}/{key}")

        for n in self.topo:
            if n.op_type == OpType.INPUT:
                with scope_of(node_key(n)):
                    vals = self._apply_view(n, [values[(n.guid, 0)]])
                values[(n.guid, 0)] = vals[0]
                continue
            if remat_groups and n.guid in self._remat_member_of:
                entry = self._remat_member_of[n.guid]
                if n.guid != entry:
                    continue  # computed by the group's checkpointed call
                values.update(self._run_remat_group(
                    remat_groups[entry], values, trainable, nontrainable, rng
                ))
                continue
            key = node_key(n)
            ins = [values[(e.src, e.src_idx)] for e in self.graph.in_edges(n)]
            params = {}
            params.update(trainable.get(key, {}))
            params.update(nontrainable.get(key, {}))
            tables = page_tables
            if classes is not None:
                # the node's class of the launch's two tables
                with jax.named_scope(scopes.UNPACK):
                    tables = page_tables[classes.get(key, 0)]
            ctx = LowerCtx(
                training=training,
                rng=jax.random.fold_in(rng, n.guid) if rng is not None else None,
                mesh=self.mesh,
                seq_length=self.seq_length,
                node_guid=n.guid,
                sharding=n.sharding,
                kv_cache=(kv_caches.get(key) if kv_caches is not None
                          else None),
                cache_position=cache_position,
                page_tables=tables,
                ragged_q_lens=ragged_q_lens,
                ragged_depths=ragged_depths,
                ragged_anc=ragged_anc,
                state_slots=state_slots,
            )
            if (
                skip_sink_softmax
                and n is self.sink
                and n.op_type == OpType.SOFTMAX
            ):
                outs = self._apply_view(n, [ins[0]])
                values[(n.guid, 0)] = outs[0]
                continue
            lowering = get_lowering(n.op_type)
            # named_scope stamps this node's stable key into the HLO
            # metadata op_name of every instruction it traces (backward
            # included: transpose/jvp wrappers keep the scope name), so
            # analysis.hloaudit can attribute lowered collectives back to
            # PCG nodes and diff them against the cost model's manifest
            with scope_of(key):
                if (
                    training
                    and self.remat == "attention"
                    and n.op_type
                    in (OpType.MULTIHEAD_ATTENTION, OpType.RING_ATTENTION)
                ):
                    # recompute S×S attention probs in backward instead of
                    # saving them (reference has no remat; on TPU this
                    # trades cheap MXU FLOPs for the scarce HBM)
                    outs = jax.checkpoint(
                        lambda ps, xs: lowering(n.attrs, list(xs), ps, ctx)
                    )(params, tuple(ins))
                else:
                    outs = lowering(n.attrs, ins, params, ctx)
                outs = self._apply_view(n, outs)
            for i, o in enumerate(outs):
                values[(n.guid, i)] = o
            if ctx.state_updates:
                aux = ctx.state_updates.pop("__aux_loss__", None)
                if aux is not None:
                    aux_loss = aux_loss + aux
                if ctx.state_updates:
                    state_updates[key] = dict(ctx.state_updates)
            if ctx.cache_updates and cache_out is not None:
                cache_out[key] = dict(ctx.cache_updates)
        return values[(self.sink.guid, 0)], state_updates, aux_loss

    def _run_remat_group(self, group, values, trainable, nontrainable, rng):
        """Execute one remat="hidden" group under jax.checkpoint: only the
        group's external inputs are saved for backward; the hidden
        activations inside are recomputed. Returns {out_key: value}. A
        SwiGLU diamond split by column over one mesh axis runs per shard
        (runtime/column_group.py): its input gradient is reduced once."""
        from flexflow_tpu.runtime import column_group

        members, _, out_key, ext = group
        gparams = {node_key(gn): {**trainable.get(node_key(gn), {}),
                                  **nontrainable.get(node_key(gn), {})}
                   for gn in members}
        split = column_group.column_split(self.graph, self.mesh, members)

        def lower(gn, local, gp, constrain=True):
            ins = [local[(e.src, e.src_idx)]
                   for e in self.graph.in_edges(gn)]
            ctx = LowerCtx(
                training=True, mesh=self.mesh, seq_length=self.seq_length,
                node_guid=gn.guid, sharding=gn.sharding,
                rng=(jax.random.fold_in(rng, gn.guid)
                     if rng is not None else None))
            with jax.named_scope(node_key(gn)):
                outs = get_lowering(gn.op_type)(
                    gn.attrs, ins, gp.get(node_key(gn), {}), ctx)
                if constrain:
                    outs = self._apply_view(gn, outs)
            local.update({(gn.guid, i): o for i, o in enumerate(outs)})

        def group_fn(gp, *xs):
            local = dict(zip(ext, xs))
            if split is not None:
                column_group.run_split(split, self.mesh, lower, local, gp)
            for gn in (members if split is None else split.rest):
                lower(gn, local, gp)
            return local[out_key]

        return {out_key: jax.checkpoint(group_fn)(
            gparams, *[values[k] for k in ext])}

    # ------------------------------------------------------------------
    # compiled steps

    def _maybe_repeat_labels(self, labels):
        """AggregateSpec repl_labels (model.cc:2875): k logit rows per
        sample need each label k times."""
        if self.label_repeats > 1:
            return jnp.repeat(labels, self.label_repeats, axis=0)
        return labels

    def _rescale_correct(self, step_metrics):
        """Slot-average the correct count so it stays on the per-SAMPLE
        scale fit()/eval() sum."""
        if self.label_repeats > 1 and "accuracy_correct" in step_metrics:
            step_metrics["accuracy_correct"] = (
                step_metrics["accuracy_correct"] / self.label_repeats
            )
        return step_metrics

    @staticmethod
    def _merge_state(nontrainable, updates):
        if not updates:
            return nontrainable
        new = {k: dict(v) for k, v in nontrainable.items()}
        for nk, ws in updates.items():
            new.setdefault(nk, {}).update(ws)
        return new

    def train_step(self):
        if self._train_step is not None:
            return self._train_step
        from flexflow_tpu.obs import scopes
        opt = self.optimizer
        fused = self.fuse_loss_softmax
        sink_is_sm = self.last_op_is_softmax and not fused

        def step(trainable, nontrainable, opt_state, rng, labels, *inputs):
            labels = self._maybe_repeat_labels(labels)

            def loss_fn(tr):
                with jax.named_scope(scopes.FORWARD):
                    logits, updates, aux = self.run_forward(
                        tr, nontrainable, inputs, training=True, rng=rng,
                        skip_sink_softmax=fused)
                    loss = compute_loss(self.loss_type, logits, labels, sink_is_sm)
                return loss + aux, (logits, updates, loss)

            grads, (logits, updates, loss) = jax.grad(loss_fn, has_aux=True)(trainable)
            opt_sh = getattr(self, "_opt_shardings", None)
            with jax.named_scope(scopes.OPTIMIZER):
                new_tr, new_opt = opt.update(grads, trainable, opt_state)
                if opt_sh is not None and self.zero_sharded_opt:
                    # keep ZeRO layout stable across steps; with the state
                    # sharded over data, XLA lowers the grad psum feeding the
                    # update into reduce-scatter + all-gather of new params
                    new_opt = jax.tree.map(
                        jax.lax.with_sharding_constraint, new_opt, opt_sh)
            new_ntr = self._merge_state(nontrainable, updates)
            with jax.named_scope(scopes.STEP_METRICS):
                step_metrics = self._rescale_correct(compute_step_metrics(
                    self.metrics, self.loss_type, logits, labels, sink_is_sm))
            step_metrics["loss"] = loss
            return new_tr, new_ntr, new_opt, step_metrics

        donate = (0, 1, 2) if self.donate else ()
        self._train_step = _TracedStep(
            jax.jit(step, donate_argnums=donate), "train_step")
        return self._train_step

    def eval_step(self):
        if self._eval_step is not None:
            return self._eval_step
        from flexflow_tpu.obs import scopes
        fused = self.fuse_loss_softmax
        sink_is_sm = self.last_op_is_softmax and not fused

        def step(trainable, nontrainable, labels, *inputs):
            labels = self._maybe_repeat_labels(labels)
            with jax.named_scope(scopes.FORWARD):
                logits, _, _ = self.run_forward(
                    trainable, nontrainable, inputs, training=False,
                    rng=jax.random.key(0), skip_sink_softmax=fused)
                loss = compute_loss(self.loss_type, logits, labels, sink_is_sm)
            with jax.named_scope(scopes.STEP_METRICS):
                m = self._rescale_correct(compute_step_metrics(
                    self.metrics, self.loss_type, logits, labels, sink_is_sm))
            m["loss"] = loss
            return m

        self._eval_step = _TracedStep(jax.jit(step), "eval_step")
        return self._eval_step

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """Per-attention-node K/V buffers for autoregressive decoding
        (net-new vs the reference, which has no generation path). Buffer
        dtype follows each attention's activation dtype unless given.
        RING_ATTENTION nodes decode through the shared MHA cache path
        (decode is sequential — no sequence to shard); PIPELINE
        composites get layer-stacked (L, b, maxlen, kv, hd) buffers
        threaded through their layer scan."""
        caches = {}
        for n in self.topo:  # fflint: host-ok (one-time cache init)
            ins = self.graph.input_shapes(n)
            dt = dtype
            if dt is None:
                dt = ins[0].dtype.jnp_dtype if ins else jnp.bfloat16
            if n.op_type in (OpType.MULTIHEAD_ATTENTION,
                             OpType.RING_ATTENTION):
                shape = (batch, max_len, n.attrs.num_kv, n.attrs.kdim)
            elif n.op_type == OpType.PIPELINE:
                dim = ins[0].dims[-1].size
                shape = (n.attrs.layers, batch, max_len, n.attrs.kv_heads,
                         dim // n.attrs.heads)
            else:
                continue
            caches[node_key(n)] = {
                "k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)
            }
        if not caches:
            raise ValueError(
                "generate() needs attention nodes (MULTIHEAD_ATTENTION, "
                "RING_ATTENTION, or a PIPELINE composite)"
            )
        return caches

    def page_classes(self) -> Optional[Dict[str, int]]:
        """{node key: 0 (full) or 1 (window)} over the paged attention
        nodes of a graph that has sliding-window layers, else None: one
        class of pages, one table, as every graph had before. Decided by
        what the graph holds and by no option."""
        cls = {node_key(n): int(getattr(n.attrs, "window", None)
                                is not None)
               for n in self.topo if n.op_type in PAGED_ATTENTION_OPS}
        return cls if any(cls.values()) else None

    def window_rows(self) -> int:
        """The widest sliding window among the graph's layers (0: none);
        what the window class of pages is sized by."""
        return max((n.attrs.window or 0 for n in self.topo
                    if n.op_type in PAGED_ATTENTION_OPS
                    and hasattr(n.attrs, "window")), default=0)

    def state_layers(self) -> List[str]:
        """Keys of the nodes that keep a per-slot state (`STATE_OPS`), in
        graph order; empty for a graph of attention layers alone."""
        return [node_key(n) for n in self.topo if n.op_type in STATE_OPS]

    def node_groups(self) -> Dict[str, str]:
        """{node key: group} for every node of the graph: what the node is
        FOR, one of `obs.scopes.GROUPS`, derived from its `OpType` and its
        place in the graph and never from the spelling of its key. `attn`
        the attention nodes (full, window, latent), `state` the KDA and
        Mamba-2 mixers, `experts` an expert layer with a router that feeds
        nothing else, `head` the sink and what leads to it back to the
        final norm, `ffn` a contracting linear with the elementwise nodes
        and the expanding linears it alone consumes, `glue` the rest
        (embedding, norms, residual adds, the streams' mixing). The ragged
        step wraps each node's own scope in its group (`run_forward`)."""
        if self._node_groups is not None:
            return self._node_groups
        from flexflow_tpu.obs import scopes

        by_guid = {n.guid: n for n in self.topo}
        group: Dict[int, str] = {}
        for n in self.topo:
            if n.op_type in PAGED_ATTENTION_OPS:
                group[n.guid] = scopes.ATTN
            elif n.op_type in STATE_OPS:
                group[n.guid] = scopes.STATE
            elif n.op_type in _EXPERT_OPS:
                group[n.guid] = scopes.EXPERTS
        # the head: from the sink back along first inputs to the final norm
        chain, n = [], self.sink
        while n.op_type in _HEAD_OPS and n.guid not in group:
            chain.append(n)
            ins = self.graph.in_edges(n)
            if n.op_type in _NORM_OPS or not ins:
                break
            n = by_guid[ins[0].src]
        if any(n.op_type in (OpType.LINEAR, OpType.TIED_HEAD)
               for n in chain):
            group.update((n.guid, scopes.HEAD) for n in chain)

        def consumers(n):
            return [by_guid[e.dst] for e in self.graph.out_edges(n)]

        def widens(n):
            return (n.outputs[0].dims[-1].size
                    > self.graph.input_shapes(n)[0].dims[-1].size)

        for n in reversed(self.topo):
            if n.guid in group:
                continue
            users = consumers(n)
            if n.op_type in _ROUTER_OPS and users and all(
                    group.get(u.guid) == scopes.EXPERTS for u in users):
                group[n.guid] = scopes.EXPERTS
            elif n.op_type == OpType.LINEAR and not widens(n):
                # a feed-forward's `down`: back through the activation
                # and the product to the linears that widen the row
                members, todo, whole = {n.guid: n}, [n], True
                while todo and whole:
                    for e in self.graph.in_edges(todo.pop()):
                        m = by_guid[e.src]
                        if m.guid in members:
                            continue
                        if m.guid in group or not (
                                m.op_type in _ELEMENTWISE_OPS
                                or m.op_type == OpType.LINEAR and widens(m)):
                            whole = False
                            break
                        members[m.guid] = m
                        if m.op_type != OpType.LINEAR:
                            todo.append(m)
                whole = whole and all(
                    u.guid in members for m in members.values()
                    if m is not n for u in consumers(m))
                if whole and len(members) > 1:
                    group.update((g, scopes.FFN) for g in members)
        self._node_groups = {
            node_key(n): group.get(n.guid, scopes.GLUE) for n in self.topo}
        return self._node_groups

    def state_kinds(self) -> Tuple[str, ...]:
        """The kinds of state layer the graph holds (`STATE_KINDS`), in
        `STATE_OPS` order."""
        there = {n.op_type for n in self.topo}
        return tuple(STATE_KINDS[op] for op in STATE_OPS if op in there)

    def paged_kv_cache_specs(self, num_pages: int, page_size: int,
                             dtype=None, num_pages_window: Optional[int]
                             = None, slots: Optional[int] = None
                             ) -> Dict[str, Dict[str, Any]]:
        """Shape/dtype specs (jax.ShapeDtypeStruct) of the paged K/V
        pools init_paged_kv_cache materializes — also the abstract
        arguments lowered_modules() feeds the paged entry points, so the
        audit lowering and the real server always agree on shapes. A
        QUANTIZED pool dtype (int8) adds the per-(page, head) scale
        sidecar entries "k_scale"/"v_scale" — (num_pages, num_kv)
        float32 — to every node's dict (paged/quant.py has the layout
        story); putting them inside the same dict is what lets the COW
        clone, the defrag permutation and the spec commit move scales
        with their pages by construction.

        A sliding-window node's pool has `num_pages_window` pages, its
        own class's count (`page_classes`; a server sizes it to a window
        and a chunk a slot, not to whole sequences).

        A STATE node (`STATE_OPS`) has a fourth kind of leaf, indexed by
        SLOT and not by page: what its attrs' `state_specs(slots)`
        names ("s" float32, "conv" at the activations' dtype: a KDA
        node's (slots, H, d, d) and 3 H d lanes of conv rows, a Mamba-2
        node's (slots, H, P, N) and H P + 2 N lanes), whatever the
        pool's dtype. Such a graph needs `slots`."""
        from flexflow_tpu.paged.quant import is_quantized_dtype

        classes = self.page_classes() or {}
        # a window node's class has its own count (not said: the same)
        pages_of = (num_pages, num_pages_window or num_pages)
        specs = {}
        for n in self.topo:
            if n.op_type == OpType.PIPELINE:
                raise ValueError(
                    "paged decode does not support PIPELINE composite "
                    "graphs (their KV cache is threaded through the layer "
                    "scan); serve with paged=False"
                )
            if n.op_type in STATE_OPS:
                if slots is None:
                    raise ValueError(
                        "a graph with state layers keeps a state a SLOT: "
                        "paged_kv_cache_specs needs `slots`")
                act = self.graph.input_shapes(n)[0].dtype.jnp_dtype
                specs[node_key(n)] = {
                    name: jax.ShapeDtypeStruct(shape, dt_ or act)
                    for name, (shape, dt_) in n.attrs.state_specs(
                        int(slots)).items()}
                continue
            if n.op_type not in PAGED_ATTENTION_OPS:
                continue
            ins = self.graph.input_shapes(n)
            dt = dtype
            if dt is None:
                dt = ins[0].dtype.jnp_dtype if ins else jnp.bfloat16
            num_pages = pages_of[classes.get(node_key(n), 0)]
            if n.op_type == OpType.LATENT_ATTENTION:
                # ONE entry a node: a token's row is [c_kv | k_r], key and
                # value of every head at once (paged/latent.py)
                from flexflow_tpu.paged.latent import pool_lanes

                if is_quantized_dtype(dt):
                    raise ValueError(
                        "kv_dtype='int8' is not supported for a latent "
                        "attention pool: the scale sidecar is per kv head "
                        "and a latent row has none")
                specs[node_key(n)] = {"c": jax.ShapeDtypeStruct(
                    (num_pages, page_size,
                     pool_lanes(n.attrs.latent_width)), dt)}
                # a SPARSE latent layer keeps a second row of another
                # grain on the same pages: one pooled indexer key a
                # block of `index_pool` tokens ("kp"). A leaf of the same
                # dict, so a page's clone, release, preemption and the
                # defrag permutation move it with the latent rows
                pooled = n.attrs.index_pool_specs(num_pages, page_size)
                if pooled is not None:
                    specs[node_key(n)]["kp"] = jax.ShapeDtypeStruct(
                        pooled, dt)
                continue
            shape = (num_pages, page_size, n.attrs.num_kv * n.attrs.kdim)
            specs[node_key(n)] = {
                "k": jax.ShapeDtypeStruct(shape, dt),
                "v": jax.ShapeDtypeStruct(shape, dt),
            }
            if is_quantized_dtype(dt):
                sshape = (num_pages, n.attrs.num_kv)
                specs[node_key(n)]["k_scale"] = jax.ShapeDtypeStruct(
                    sshape, jnp.float32)
                specs[node_key(n)]["v_scale"] = jax.ShapeDtypeStruct(
                    sshape, jnp.float32)
        if not specs:
            raise ValueError(
                "paged decode needs attention nodes (MULTIHEAD_ATTENTION "
                "or RING_ATTENTION)"
            )
        return specs

    def init_paged_kv_cache(self, num_pages: int, page_size: int,
                            dtype=None, num_pages_window: Optional[int]
                            = None, slots: Optional[int] = None):
        """Per-attention-node paged K/V POOLS for the paged decode path
        (flexflow_tpu.paged): flat-lane (num_pages, page_size, Hkv*D)
        buffers (paged/attention.py has the layout story) shared by
        every request through per-slot page tables, so HBM scales with
        TOKENS IN FLIGHT instead of slots x max_len. PIPELINE
        composites keep their layer-scan threaded dense caches and are
        not paged (their cache lives inside the scan carry)."""
        specs = self.paged_kv_cache_specs(num_pages, page_size, dtype,
                                          num_pages_window, slots)
        # a pool is born COMMITTED to its device, as every launch's output
        # pool is (the sharding a launch gives its outputs: replicated over
        # the model's mesh): a launch shape then has ONE jit signature and
        # compiles once. The serving programs CONSUME the pool they are
        # given (donate_argnums) and return it written in place, so there
        # is one live buffer a pool from here on (docs/paged.md "Who owns
        # the pool")
        where = self.launch_placement()
        return jax.tree.map(
            lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), where),
            specs)

    def launch_placement(self):
        """Where a serving launch puts its outputs (replicated over the
        model's mesh): what a buffer that re-enters launches is born
        committed to, so a launch shape has one jit signature."""
        from jax.sharding import NamedSharding, PartitionSpec

        return (NamedSharding(self.mesh, PartitionSpec())
                if self.mesh is not None else jax.devices()[0])

    def ragged_step_fn(self):
        """jitted (params, pools, page_tables, pos, q_lens, depths, anc,
        ids, feed=None) -> (probs, new_pools): ONE ragged paged step over a packed
        batch of work items — decode rows, prefill chunks and drafted
        trees in the same launch (flexflow_tpu.paged.attention). Each
        batch entry b carries q_lens[b] live rows of the (B, S) ids
        window writing K/V at pos[b]..pos[b]+q_lens[b]-1 through its
        table row, scoring at pos[b] + depths[b] under the anc[b]
        window visibility; entries padded to the launch shape pass
        q_len 0 and do no work. Compiled once per (B, S) launch shape —
        the scheduler packs items into a small set of launch shapes, so
        admission order and work mix never recompile it. `feed`, a
        server's ((B,) int32 slot or -1, (slots,) int32 newest tokens),
        takes an entry's first id from the device (a decode row of the
        launch after the one that picked its token); without it the
        program is the one it always was. `packed`, a server's ONE upload
        a launch (`launch_columns`: ids, pos, q_lens, slots, feed slots
        and table rows in one (B, cols) int32 array), is sliced apart in
        the program where `page_tables`, `pos`, `q_lens` and `ids` are
        passed None, `feed` is (None, newest) and `state_slots` is left
        out: the same program but for the slices, and without the keyword
        the positional form to the byte.

        The pools are DONATED: the K/V rows are scattered into the
        buffers passed in, which are gone for the caller (rebind the
        returned pools; docs/paged.md "Who owns the pool"). Undonated,
        XLA copies every pool before the scatter of a few rows, every
        layer, every launch."""
        if self._ragged_step_fn is not None:
            return self._ragged_step_fn

        from flexflow_tpu.obs import scopes

        classes = 1 if self.page_classes() is None else 2
        stateful = bool(self.state_layers())

        def unpack(page_tables, pos, q_lens, window, inputs, feed,
                   state_slots, packed):
            """What the step does before its nodes, under `scopes.UNPACK`:
            the descriptor's slices and the fed ids."""
            if packed is not None:
                # ONE UPLOAD A LAUNCH: static slices of the descriptor
                # stand where the positional operands (None then) did
                at, _ = launch_columns(window, classes,
                                       width=packed.shape[1])
                tables = [packed[:, cols] for cols in at["tables"]]
                page_tables = (tables[0] if classes == 1
                               else jnp.stack(tables))
                pos, q_lens = packed[:, at["pos"]], packed[:, at["q_lens"]]
                inputs = (packed[:, at["ids"]],) + inputs
                if stateful:
                    state_slots = packed[:, at["slot"]]
                if feed is not None:
                    feed = (packed[:, at["feed"]], feed[1])
            if feed is not None:
                # LAUNCH AHEAD: the first id of an entry whose `slot` is
                # not -1 is that slot's newest token, which the launch
                # before picked and the host has not seen yet
                slot, newest = feed
                ids = inputs[0]
                fed = jnp.where(slot >= 0, newest[jnp.maximum(slot, 0)],
                                ids[:, 0])
                inputs = (ids.at[:, 0].set(fed),) + inputs[1:]
            return page_tables, pos, q_lens, inputs, state_slots

        def step(trainable, nontrainable, caches, page_tables, pos,
                 q_lens, depths, anc, *inputs, feed=None, state_slots=None,
                 packed=None):
            with jax.named_scope(scopes.UNPACK):
                page_tables, pos, q_lens, inputs, state_slots = unpack(
                    page_tables, pos, q_lens, depths.shape[1], inputs,
                    feed, state_slots, packed)
            cache_out = {}
            out, state, _ = self.run_forward(
                trainable, nontrainable, inputs, training=False,
                rng=jax.random.key(0), kv_caches=caches,
                cache_position=pos, cache_out=cache_out,
                page_tables=page_tables, ragged=(q_lens, depths, anc),
                state_slots=state_slots,
            )
            # what the expert layers and the sparse latent layers counted
            # in this launch, one row a layer, handed back beside the
            # pools under keys no node has; the caller takes them out
            # before the pools go into the next launch
            for stat, key in (("moe_stats", LAUNCH_STATS),
                              ("dsa_stats", LAUNCH_DSA_STATS)):
                rows = [st[stat] for _nk, st in sorted(state.items())
                        if stat in st]
                if rows:
                    with jax.named_scope(scopes.UNPACK):
                        cache_out[key] = jnp.stack(rows)
            return out, cache_out

        self._ragged_step_fn = self.compile_tracker.wrap(
            "ragged_step", jax.jit(step, donate_argnums=(2,)),
            lambda args: args[6].shape)
        return self._ragged_step_fn

    def paged_commit_fn(self):
        """jitted (pools, page_tables, src, dst) -> pools: copy the
        accepted tree path's K/V rows onto the contiguous committed
        positions (speculative rollback, flexflow_tpu.spec). src/dst are
        (slots, C) int32 cache-row positions resolved through each slot's
        page table; unused entries point a row at itself (a no-op copy),
        so one fixed-shape program serves every acceptance outcome.
        Rejected rows are NOT touched — they sit past the advanced write
        head and are masked like any stale page content. The pools are
        donated: the rows move inside the buffers passed in.

        On a QUANTIZED pool (scale sidecar present, paged/quant.py) the
        copy is scale-aware: destination pages first GROW their scales
        to cover the incoming source rows (re-quantizing their existing
        rows in place, the same grow-only discipline as append), then
        each copied row dequantizes at its source page's scale and
        re-quantizes at the destination's. Unused self-copy entries stay
        exact — the scale ratio is 1 and the int grid round-trips."""
        if self._paged_commit_fn is not None:
            return self._paged_commit_fn

        # grid and eps shared with quantized_append — one definition of
        # the int8 grid, one floor under scale ratios
        from flexflow_tpu.paged.quant import (
            SCALE_EPS,
            dequantize_pages,
            quantize_rows,
            rescale_pages,
        )

        def _copy_rows_quant(buf, sc, sp, so, dp, do):
            f32 = jnp.float32
            zero = f32(0.0)
            sc2 = sc.at[dp].max(sc[sp])
            old_d, new_d = sc[dp], sc2[dp]            # (slots, C, Hkv)
            ratio = jnp.where(new_d > 0,
                              old_d / jnp.maximum(new_d, f32(SCALE_EPS)),
                              zero)
            buf = buf.at[dp].set(rescale_pages(buf[dp], ratio))
            den = jnp.where(new_d > 0, new_d, f32(1.0))
            row = dequantize_pages(buf[sp, so][..., None, :], sc2[sp])
            row = row.reshape(*den.shape, -1)         # (slots, C, Hkv, D)
            buf = buf.at[dp, do].set(quantize_rows(row, den, buf.dtype))
            return buf, sc2

        def commit(caches, page_tables, src, dst):
            bidx = jnp.arange(src.shape[0])[:, None]
            out = {}
            for key, bufs in caches.items():
                P = bufs["k"].shape[1]
                sp, so = page_tables[bidx, src // P], src % P
                dp, do = page_tables[bidx, dst // P], dst % P
                if "k_scale" in bufs:
                    ent = {}
                    for n in ("k", "v"):
                        ent[n], ent[n + "_scale"] = _copy_rows_quant(
                            bufs[n], bufs[n + "_scale"], sp, so, dp, do)
                    out[key] = ent
                else:
                    out[key] = {
                        n: bufs[n].at[dp, do].set(bufs[n][sp, so])
                        for n in ("k", "v")
                    }
            return out

        self._paged_commit_fn = self.compile_tracker.wrap(
            "paged_commit", jax.jit(commit, donate_argnums=(0,)),
            lambda args: args[2].shape)
        return self._paged_commit_fn

    def decode_fn(self):
        """jitted (params, caches, pos, ids) -> (probs, new_caches): one
        prefill or decode step through the cached-attention lowering.
        Compiled once per input seq length (prompt prefill + S=1 steps)."""
        if self._decode_fn is not None:
            return self._decode_fn

        def step(trainable, nontrainable, caches, pos, *inputs):
            cache_out = {}
            out, _, _ = self.run_forward(
                trainable, nontrainable, inputs, training=False,
                rng=jax.random.key(0), kv_caches=caches,
                cache_position=pos, cache_out=cache_out,
            )
            return out, cache_out

        self._decode_fn = self.compile_tracker.wrap(
            "decode_step", jax.jit(step), lambda args: args[4].shape)
        return self._decode_fn

    def forward_fn(self):
        """Inference forward (predict)."""
        if self._forward is not None:
            return self._forward

        def fwd(trainable, nontrainable, *inputs):
            out, _, _ = self.run_forward(
                trainable, nontrainable, inputs, training=False, rng=jax.random.key(0)
            )
            return out

        self._forward = jax.jit(fwd)
        return self._forward

    def jit_cache_entries(self) -> int:
        """Live jitted-callable memos this executor holds (the
        ff_jit_cache_entries gauge): the single-slot factories."""
        singles = (self._train_step, self._eval_step, self._forward,
                   self._decode_fn, self._ragged_step_fn,
                   self._paged_commit_fn)
        return sum(1 for f in singles if f is not None)

    def warm_launch_shapes(self, catalog, *, params, on_probs=None,
                           newest=None) -> Dict:
        """Pre-compile every launch shape in a shapecheck catalog
        (analysis.shapecheck.enumerate_catalog) so first-request TTFT
        stops paying compile cost and steady-state serving provably
        never recompiles.

        Warming is CONCRETE calls, not AOT lowering: only a real call
        populates the jit dispatch cache the serving tick hits, so every
        argument here reproduces the server's exact avals — the int32
        packed descriptor (ids, pos, q_lens, slots, table rows: ONE array
        a launch), bool ancestor masks, float32 temps, a
        typed rng key — against throwaway zero pools built from the
        catalog's config (zeroed page tables point every row at the null
        page, so the warm writes touch nothing a request will read; the
        dummy pools are garbage the moment this returns).

        ONE pool is threaded through every call: the serving programs
        consume the pool they are given (donate_argnums) and return it
        written in place, so each call's output pool is the next call's
        input and no second pool is ever alive beside the server's. A
        pool is born committed (init_paged_kv_cache), as a launch's
        output is, so a launch shape has one jit signature and compiles
        once. Each ragged shape's first call also records how many pool
        leaves it was handed and how many came back in the SAME device buffer
        (the result's `pool_alias`): deleting the argument proves nothing
        (XLA may decline an alias and copy all the same), and the serving
        tick puts the pair on its traced launches.

        Returns {"warmed_shapes", "vocab", "probs_dtype", "probs_ref",
        "pool_alias"} — the serving layer warms its (batch, vocab)
        sampling program (the one entry the executor does not own) from
        slices of probs_ref. `on_probs`, if
        given, is called with every ragged shape's (B, W, V) output, so
        the caller can warm what it runs on a launch's probs at that
        shape. `newest`, a paged server's device vector of its slots'
        newest tokens, is fed to every ragged shape as the server feeds
        it (`ragged_step_fn`'s `feed`)."""
        import contextlib
        import time

        from flexflow_tpu import obs
        from flexflow_tpu.obs.compile_tracker import compile_split

        cfg = dict(catalog.get("config", {}))
        entries = catalog.get("entries", {})
        tr, ntr = params
        slots = int(cfg["slots"])
        warmed = 0
        probs = probs_ref = caches_c = None
        pool_alias: Dict[Tuple[int, int], Tuple[int, int]] = {}
        if cfg.get("paged", True):
            from flexflow_tpu.paged.quant import resolve_kv_dtype

            page_size = int(cfg["page_size"])
            cols = int(cfg["table_cols"])
            num_pages = int(cfg["num_pages"] or slots * cols + 1)
            pool_dt = resolve_kv_dtype(cfg.get("kv_dtype") or "auto")
            caches = self.init_paged_kv_cache(
                num_pages, page_size, dtype=pool_dt,
                num_pages_window=cfg.get("num_pages_window"), slots=slots)
            step = self.ragged_step_fn()
            # a graph with window layers launches with a table a class
            classes = 1 if self.page_classes() is None else 2
            fed = {} if newest is None else {"feed": (None, newest)}
            for B, W in entries.get(  # fflint: host-ok (one-time warmup)
                    "ragged_step", {}).get("shapes", ()):
                B, W = int(B), int(W)
                # the launch's ONE upload, as the server packs it: every
                # table row the null page's, no entry fed from `newest`
                at, width = launch_columns(W, classes, table_cols=cols)
                packed = np.zeros((B, width), np.int32)
                packed[:, at["feed"]] = -1
                deps = jnp.asarray(np.tile(
                    np.arange(W, dtype=np.int32), (B, 1)))
                anc = jnp.asarray(np.tile(
                    np.tril(np.ones((W, W), np.bool_)), (B, 1, 1)))
                before = _pool_buffers(caches)
                with obs.span("warm_shape") as sp:
                    t0 = time.monotonic()
                    with (compile_split() if sp
                          else contextlib.nullcontext()) as split:
                        probs, caches = step(
                            tr, ntr, caches, None, None, None, deps, anc,
                            packed=jnp.asarray(packed), **fed)
                    if sp:
                        # for the record of set-up: what THIS shape's
                        # first call cost (jax's own compile phases; they
                        # nest, so they need not sum to call_s) and how
                        # long its first run then kept the device
                        t1 = time.monotonic()
                        probs.block_until_ready()
                        sp.set(window=W, rows=B * W, **split,
                               call_s=t1 - t0,
                               first_run_s=time.monotonic() - t1)
                    caches.pop(LAUNCH_STATS, None)
                    caches.pop(LAUNCH_DSA_STATS, None)
                # the shape's pool leaves, and how many of them came back
                # in the device buffer they went in with
                pool_alias[(B, W)] = (len(before), sum(
                    a == b for a, b in zip(before, _pool_buffers(caches))))
                if on_probs is not None:
                    on_probs(probs)
                if probs_ref is None or B == slots:
                    probs_ref = probs
                warmed += 1
            commit = (self.paged_commit_fn()
                      if "paged_commit" in entries else None)
            for S, C in entries.get(  # fflint: host-ok (one-time warmup)
                    "paged_commit", {}).get("shapes", ()):
                z = jnp.asarray(np.zeros((int(S), int(C)), np.int32))
                caches = commit(caches, jnp.zeros((slots, cols), jnp.int32),
                                z, z)
                warmed += 1
        else:
            max_len = int(cfg["max_len"])
            caches_u = self.init_kv_cache(slots, max_len)
            pre = self.init_kv_cache(1, max_len)
            step = self.decode_fn()
            for B, L in entries.get(  # fflint: host-ok (one-time warmup)
                    "decode_step", {}).get("shapes", ()):
                B, L = int(B), int(L)
                ids = jnp.asarray(np.zeros((B, L), np.int32))
                if B == 1 and L > 1:
                    # admission prefill: one-slot staging cache (never
                    # reassigned, so never committed), the literal
                    # python 0 the admit path passes as pos
                    probs, _ = step(tr, ntr, pre, 0, ids)
                else:
                    pos = jnp.asarray(np.zeros((B,), np.int32))
                    probs, caches_out = step(tr, ntr, caches_u, pos, ids)
                    if caches_c is None:
                        caches_c = caches_out
                    probs, _ = step(tr, ntr, caches_c, pos, ids)
                    if probs_ref is None or B == slots:
                        probs_ref = probs
                warmed += 1
        return {
            "warmed_shapes": warmed,
            "vocab": int(probs.shape[-1]) if probs is not None else None,
            "probs_dtype": (str(probs.dtype) if probs is not None
                            else None),
            # real launch outputs, for the serving layer's pick warm:
            # slicing probs_ref reproduces the exact committedness (and
            # sharding) of the serve loop's pick inputs
            "probs_ref": probs_ref,
            # ragged launch shape (B, W) -> (pool leaves passed, leaves
            # that came back in the buffer they went in with)
            "pool_alias": pool_alias,
        }

    # ------------------------------------------------------------------
    # AOT lowering (analysis.hloaudit ground-truth hook)

    def abstract_params(self):
        """(trainable, nontrainable) pytrees of jax.ShapeDtypeStruct with
        the real param NamedShardings attached — the arguments init_params
        would produce, without materializing anything."""
        tr_sh, ntr_sh = self.param_shardings()
        tr, ntr = {}, {}
        for nk, ws in self.weight_specs().items():
            for wn, decl in ws.items():
                dtype = decl.shape.dtype.jnp_dtype
                if dtype == jnp.bfloat16 or dtype == jnp.float16:
                    dtype = jnp.float32  # master weights (init_params)
                sh = (tr_sh if decl.trainable else ntr_sh).get(
                    nk, {}).get(wn)
                sds = jax.ShapeDtypeStruct(
                    tuple(d for d in decl.shape.dims), dtype, sharding=sh)
                (tr if decl.trainable else ntr).setdefault(nk, {})[wn] = sds
        return tr, ntr

    def _abstract_opt_state(self, trainable):
        state = jax.eval_shape(self.optimizer.init_state, trainable)
        if self.mesh is None:
            return state
        shardings_like, repl = self.opt_state_shardings(trainable)
        ptree = jax.tree.structure(trainable)

        def tree_shardings(sub):
            if jax.tree.structure(sub) == ptree:
                return shardings_like(sub)
            return jax.tree.map(lambda _: repl, sub)

        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            state, {k: tree_shardings(v) for k, v in state.items()},
        )

    def _abstract_labels(self):
        """Label aval matching what fit()/eval() feed compute_loss for
        this graph's sink shape and loss type."""
        sink = self.sink.outputs[0]
        dims = tuple(d.size for d in sink.dims)
        if self.loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            shape = dims[:-1] if len(dims) > 2 else (dims[0],)
            return jax.ShapeDtypeStruct(shape, self.label_dtype)
        return jax.ShapeDtypeStruct(dims, jnp.float32)

    def _abstract_inputs(self):
        return [jax.ShapeDtypeStruct(
            tuple(d.size for d in n.outputs[0].dims),
            n.outputs[0].dtype.jnp_dtype) for n in self.input_nodes]

    def can_paged_decode(self) -> bool:
        """True when this graph has the shape paged decode serves: token
        inputs, a token-level (b, s, vocab) sink, attention nodes, and no
        PIPELINE composite (whose cache is threaded through the layer
        scan). A pooled-classification graph (BERT's (b, classes) head)
        has attention but nothing to decode."""
        has_attn = any(n.op_type in PAGED_ATTENTION_OPS for n in self.topo)
        no_pipe = all(n.op_type != OpType.PIPELINE for n in self.topo)
        token_in = (len(self.input_nodes) == 1
                    and self.input_nodes[0].outputs[0].ndim == 2
                    and jnp.issubdtype(
                        self.input_nodes[0].outputs[0].dtype.jnp_dtype,
                        jnp.integer))
        token_out = self.sink.outputs[0].ndim >= 3
        return has_attn and no_pipe and token_in and token_out

    def lowered_modules(self, entries: Optional[Sequence[str]] = None, *,
                        slots: int = 2, page_size: int = 16,
                        num_pages: Optional[int] = None,
                        max_nodes: int = 8,
                        kv_dtype: Optional[str] = None):
        """Named AOT lowerings of the real jitted entry points, traced on
        abstract arguments — nothing is allocated or executed. Returns
        {entry_name: jax.stages.Lowered}; callers .compile() each one to
        read optimized HLO and buffer-assignment stats (the ground truth
        analysis.hloaudit diffs the search cost model against).

        `entries` defaults to train_step + eval_step, plus
        "paged_decode" and "verify" when can_paged_decode(). Those two
        are two SHAPES of the one paged step program, ragged_step_fn(),
        lowered as a server launches them (pools donated): the
        (slots, 1) decode launch, and the (slots, max_nodes) launch a
        speculative server verifies drafted trees with. The paged
        shapes (slots / page_size / pool size / tree width) only scale
        the audit's byte counts, not which collectives appear. They are
        lowered against the weights a server launches with
        (serving_weights.serving_params of the abstract masters: the
        leaves the step only converts to their declared dtype arrive at
        that dtype), train_step and eval_step against the masters.
        `kv_dtype` lowers the paged entries against a quantized pool
        ("int8" adds the scale sidecar to the cache avals, paged/quant)
        so the audit prices the int8 payload bytes, not the fp ones."""
        known = ("train_step", "eval_step", "paged_decode", "verify")
        if entries is None:
            entries = ["train_step", "eval_step"]
            if self.can_paged_decode():
                entries += ["paged_decode", "verify"]
        unknown = sorted(set(entries) - set(known))
        if unknown:
            raise ValueError(f"unknown entry point(s) {unknown}; "
                             f"known: {list(known)}")
        tr, ntr = self.abstract_params()
        rng = jax.eval_shape(lambda: jax.random.key(0))
        labels = self._abstract_labels()
        inputs = self._abstract_inputs()
        out: Dict[str, Any] = {}
        if "train_step" in entries:
            if self.optimizer is None:
                raise ValueError("train_step lowering needs an optimizer")
            opt_state = self._abstract_opt_state(tr)
            out["train_step"] = self.train_step().lower(
                tr, ntr, opt_state, rng, labels, *inputs)
        if "eval_step" in entries:
            out["eval_step"] = self.eval_step().lower(
                tr, ntr, labels, *inputs)
        if {"paged_decode", "verify"} & set(entries):
            seq = self.input_nodes[0].outputs[0].dims[1].size
            max_pages = -(-(seq + max_nodes) // page_size)
            pages = (num_pages if num_pages is not None
                     else slots * max_pages + 1)
            from flexflow_tpu.paged.quant import resolve_kv_dtype

            caches = self.paged_kv_cache_specs(
                pages, page_size, dtype=resolve_kv_dtype(kv_dtype),
                slots=slots)
            from flexflow_tpu.runtime.serving_weights import serving_params

            tr, ntr = serving_params(self, (tr, ntr))
            for entry, window in (("paged_decode", 1),
                                  ("verify", max_nodes)):
                if entry not in entries:
                    continue
                out[entry] = self.ragged_step_fn().lower(
                    tr, ntr, caches,
                    *self.ragged_step_avals(slots, window, max_pages))
        return out

    def ragged_step_avals(self, slots: int, window: int, table_cols: int):
        """ragged_step_fn()'s arguments after the pools, abstract:
        (tables, pos, q_lens, depths, anc, ids) of a (slots, window)
        launch. q_lens is all 1 for a decode launch and a tree's node
        count for a verify, which only the values say. A graph with
        window layers takes a table a class of pages, stacked."""
        per_slot = jax.ShapeDtypeStruct((slots,), jnp.int32)
        rows = jax.ShapeDtypeStruct((slots, window), jnp.int32)
        tables = ((slots, table_cols) if self.page_classes() is None
                  else (2, slots, table_cols))
        return (jax.ShapeDtypeStruct(tables, jnp.int32),
                per_slot, per_slot, rows,
                jax.ShapeDtypeStruct((slots, window, window), jnp.bool_),
                rows)

    def dtype_plan(self, entries: Optional[Sequence[str]] = None, *,
                   kv_dtype: Optional[str] = None) -> Dict[str, Dict]:
        """The DECLARED per-entry numerics plan, in HLO dtype names, that
        numcheck's HLO arm diffs each lowered module against. Metadata of
        the graph's weight declarations and cache specs; only the paged
        entries trace (once: serving_weights.served_dtypes).

        Per entry: "compute" (the dtype the weights arrive at — f32 for
        train_step and eval_step, since abstract_params promotes
        bf16/f16 declarations to f32 master weights and that is what
        they are lowered against; for the paged entries the narrowest
        float dtype of the tree a server launches with, the declared
        bf16 of a llama: leaves the step reads wider, a norm's scale,
        stay f32 and are in "allowed"),
        "accum" (what a contraction ACCUMULATES at: f32. A `dot` of the
        CPU's module states it, the CPU's compiler widening any narrower
        one; the TPU's MXU accumulates f32 whatever the result's type,
        which there says where the sum is ROUNDED, so what crosses a mesh
        axis: f32 for a LINEAR's sums of activations, the activations'
        dtype for its kernel's gradient, ops/jax_ops.py `contraction`),
        "kv" (the paged entries' pool payload dtype; s8 carries the scale
        sidecar), "allowed" (every float/payload dtype the entry may
        touch: converts outside it are hlo-unplanned-convert), and
        "allow_f64": False (a weak-type promotion, hlo-unexpected-f64)."""
        known = ("train_step", "eval_step", "paged_decode", "verify")
        if entries is None:
            entries = ["train_step", "eval_step"]
            if self.can_paged_decode():
                entries += ["paged_decode", "verify"]
        unknown = sorted(set(entries) - set(known))
        if unknown:
            raise ValueError(f"unknown entry point(s) {unknown}; "
                             f"known: {list(known)}")
        declared = {"f32"}  # master weights / loss math
        for ws in self.weight_specs().values():
            for decl in ws.values():
                dt = jnp.dtype(decl.shape.dtype.jnp_dtype)  # fflint: host-ok (dtype metadata, no device dispatch)
                if jnp.issubdtype(dt, jnp.floating):  # fflint: host-ok (dtype metadata, no device dispatch)
                    declared.add(_HLO_DTYPE_NAMES.get(dt.name, dt.name))
        from flexflow_tpu.paged.quant import kv_dtype_info

        info = kv_dtype_info(kv_dtype)
        if info is not None:
            kv_name = _HLO_DTYPE_NAMES.get(info[0], info[0])
        else:
            # the cache-spec default: the attention input's own dtype
            attn = [n for n in self.topo
                    if n.op_type in PAGED_ATTENTION_OPS]
            kv_name = "bf16"
            if attn:
                ins = self.graph.input_shapes(attn[0])
                if ins:
                    dt = jnp.dtype(ins[0].dtype.jnp_dtype)
                    kv_name = _HLO_DTYPE_NAMES.get(dt.name, dt.name)
        paged = {"paged_decode", "verify"} & set(entries)
        served = "f32"
        if paged:
            from flexflow_tpu.runtime.serving_weights import serving_params

            dts = {jnp.dtype(x.dtype) for x in jax.tree.leaves(
                serving_params(self, self.abstract_params()))}
            name = min((d for d in dts if jnp.issubdtype(d, jnp.floating)),
                       key=lambda d: d.itemsize).name
            served = _HLO_DTYPE_NAMES.get(name, name)
        plan: Dict[str, Dict] = {}
        for entry in entries:
            allowed = set(declared)
            kv = None
            if entry in paged:
                kv = kv_name
                allowed.add(kv_name)
                if kv_name == "s8":
                    allowed.add("f32")  # dequant target / scale sidecar
            plan[entry] = {
                "compute": served if entry in paged else "f32",
                "accum": "f32",
                "kv": kv,
                "allowed": sorted(allowed),
                "allow_f64": False,
            }
        return plan

    # ------------------------------------------------------------------

    def batch_sharding(self, ndim: int, batch_size: Optional[int] = None):
        """Sharding for a host batch array; None when the batch dim is not
        divisible by the data group (then it stays replicated, matching
        compile()'s input-view rule). Under the submesh split the batch
        rides the widest divisible data x data_sub group — the same spec
        _apply_strategy assigns to INPUT nodes."""
        from jax.sharding import NamedSharding

        from flexflow_tpu.parallel.sharding import (
            data_batch_spec,
            group_degree,
        )

        if self.mesh is None:
            return None
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if sizes.get("data", 1) * sizes.get("data_sub", 1) <= 1:
            return None
        if batch_size is None:
            # legacy path (no divisibility info): plain data-axis sharding,
            # only meaningful when the mesh actually has a data axis
            if sizes.get("data", 1) <= 1:
                return None
            spec = batch_spec(ndim)
        else:
            spec = data_batch_spec(ndim, batch_size, sizes)
            deg = group_degree(spec[0], sizes)
            if deg <= 1 or batch_size % deg != 0:
                return None
        return NamedSharding(self.mesh, spec_to_partition_spec(spec))

    @cached_property
    def served_dtypes_memo(self) -> Dict:
        """serving_weights.served_dtypes' results for this graph, by the
        stored dtypes they were asked about: one trace of the serving
        steps an executor, shared by its servers, lowered_modules() and
        dtype_plan()."""
        return {}
