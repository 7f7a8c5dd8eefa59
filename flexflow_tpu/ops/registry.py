"""Lowering registry: OpType -> JAX lowering function.

A lowering has signature `fn(attrs, inputs, params, ctx) -> list[Array]`
where `params` is the op's weight dict and `ctx` a LowerCtx. This replaces
the reference's per-op Legion task bodies + kernel wrappers
(e.g. Linear::forward_task -> forward_kernel_wrapper, linear.cc:370,
kernels/linear_kernels.cu:83): on TPU every op lowers inline into the single
traced step function and XLA fuses/schedules.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

from flexflow_tpu.ffconst import OpType


@dataclasses.dataclass
class LowerCtx:
    """Per-trace lowering context."""

    training: bool = True
    rng: Optional[object] = None  # jax PRNG key, folded per-op by the executor
    mesh: Optional[object] = None
    seq_length: Optional[int] = None  # FFIterationConfig truncation
    node_guid: int = 0
    # the node's assigned ShardingView (composites like PIPELINE dispatch
    # on it: a pipe-sharded view selects the GPipe schedule)
    sharding: Optional[object] = None
    # autoregressive decoding (net-new vs the reference): when kv_cache is
    # set ({"k","v"} buffers for THIS attention node) the MHA lowering
    # attends over the cache at cache_position and writes the updated
    # buffers into cache_updates
    kv_cache: Optional[dict] = None
    cache_position: Optional[object] = None
    # paged decode (flexflow_tpu.paged): kv_cache buffers are a global
    # page POOL (num_pages, page_size, Hkv, D) and page_tables maps each
    # decode slot's positions onto pool pages ((slots, max_pages) int32)
    page_tables: Optional[object] = None
    # the ragged work descriptor (flexflow_tpu.paged.attention module
    # docstring): with page_tables set, every paged step — decode,
    # chunked prefill, speculative tree verify — carries per-slot
    # ragged_q_lens ((B,) int32 live query rows), ragged_depths
    # ((B, S) int32 — row i scores at absolute position
    # cache_position + depth, so sibling tree branches share one) and
    # ragged_anc ((B, S, S) bool window visibility: tril for causal
    # chains, ancestor-or-self for trees)
    ragged_q_lens: Optional[object] = None
    ragged_depths: Optional[object] = None
    ragged_anc: Optional[object] = None
    # (B,) int32: the SLOT whose recurrent state item b continues (a graph
    # with state layers, ops/kda_attention.py); None: item b is slot b
    state_slots: Optional[object] = None
    cache_updates: Dict[str, object] = dataclasses.field(default_factory=dict)
    # lowering writes non-trainable state updates here (BatchNorm running
    # stats, Cache buffers): key = weight name within the op
    state_updates: Dict[str, object] = dataclasses.field(default_factory=dict)


_LOWERINGS: Dict[OpType, Callable] = {}


def register_lowering(op_type: OpType):
    def deco(fn):
        _LOWERINGS[op_type] = fn
        return fn

    return deco


def get_lowering(op_type: OpType) -> Callable:
    # imports populate the registry on first use
    from flexflow_tpu.ops import jax_ops  # noqa: F401
    from flexflow_tpu.parallel import parallel_ops  # noqa: F401

    if op_type not in _LOWERINGS:
        raise NotImplementedError(f"no lowering registered for {op_type}")
    return _LOWERINGS[op_type]
