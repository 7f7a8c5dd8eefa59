"""Framework-wide enums.

Mirrors the public enum surface of the reference's `include/flexflow/ffconst.h`
(op types, activation modes, aggregation modes, loss/metrics types, parameter
sync modes) re-expressed for a JAX/TPU backend: DataType carries a jnp dtype,
ParamSyncType distinguishes replicated-psum vs sharded optimizer state instead
of PS/NCCL.
"""

from __future__ import annotations

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.value)

    @property
    def size_bytes(self) -> int:
        return jnp.dtype(self.value).itemsize

    @classmethod
    def from_jnp(cls, dtype) -> "DataType":
        return cls(jnp.dtype(dtype).name)


class _Coercible:
    """Mixin for enums the layer builders accept as enum | str | None.
    Coercion happens at the builder boundary so attrs always carry the
    enum (lowerings and search predicates compare against enum members —
    a stored str would silently fail those comparisons)."""

    @classmethod
    def coerce(cls, value):
        if value is None and hasattr(cls, "NONE"):
            return cls.NONE
        if isinstance(value, cls):
            return value
        return cls(str(value).lower())


class ActiMode(_Coercible, enum.Enum):
    NONE = "none"
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"
    SILU = "silu"


class AggrMode(_Coercible, enum.Enum):
    """Embedding aggregation (reference: AGGR_MODE_{NONE,SUM,AVG})."""

    NONE = "none"
    SUM = "sum"
    AVG = "avg"


class PoolType(_Coercible, enum.Enum):
    MAX = "max"
    AVG = "avg"


class LossType(enum.Enum):
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR_AVG_REDUCE = "mean_squared_error_avg_reduce"
    MEAN_SQUARED_ERROR_SUM_REDUCE = "mean_squared_error_sum_reduce"
    IDENTITY = "identity"


class MetricsType(enum.Enum):
    ACCURACY = "accuracy"
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR = "mean_squared_error"
    ROOT_MEAN_SQUARED_ERROR = "root_mean_squared_error"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"


class ParamSyncType(enum.Enum):
    """Gradient/parameter synchronization mode.

    Reference `ParameterSyncType::{NONE,PS,NCCL}` (config.h:55-59). On TPU the
    allreduce is a psum emitted by the SPMD partitioner; SHARDED keeps
    optimizer state sharded over the data axis (ZeRO-style reduce-scatter),
    which has no reference analog but is the idiomatic TPU upgrade.
    """

    NONE = "none"
    PSUM = "psum"
    SHARDED = "sharded"


class CompMode(enum.Enum):
    TRAINING = "training"
    INFERENCE = "inference"


class OpType(enum.Enum):
    """Operator types — the PCG node vocabulary.

    Covers every op in the reference's `src/ops/` + `src/parallel_ops/`
    (SURVEY.md §2.2/§2.3) plus TPU-native additions (RING_ATTENTION,
    ALL_TO_ALL for sequence parallelism; PIPELINE implemented, not a stub).
    """

    # sources
    INPUT = "input"
    WEIGHT = "weight"
    NOOP = "noop"
    # dense/conv
    CONV2D = "conv2d"
    LINEAR = "linear"
    EMBEDDING = "embedding"
    BATCH_MATMUL = "batch_matmul"
    # h E^T on an embedding's own table (a tied output head)
    TIED_HEAD = "tied_head"
    # attention
    MULTIHEAD_ATTENTION = "multihead_attention"
    RING_ATTENTION = "ring_attention"
    LATENT_ATTENTION = "latent_attention"
    KDA_ATTENTION = "kda_attention"
    # a Mamba-2 state-space mixer: a second layer with a state a slot
    MAMBA2 = "mamba2"
    # elementwise
    ELEMENT_BINARY = "element_binary"
    ELEMENT_UNARY = "element_unary"
    # shape
    RESHAPE = "reshape"
    FLAT = "flat"
    TRANSPOSE = "transpose"
    REVERSE = "reverse"
    CONCAT = "concat"
    SPLIT = "split"
    # norm / misc
    POOL2D = "pool2d"
    BATCH_NORM = "batch_norm"
    LAYER_NORM = "layer_norm"
    RMS_NORM = "rms_norm"
    SOFTMAX = "softmax"
    DROPOUT = "dropout"
    CAST = "cast"
    GATHER = "gather"
    REDUCE_SUM = "reduce_sum"
    MEAN = "mean"
    # recurrent (reference legacy NMT app, nmt/rnn.h)
    LSTM = "lstm"
    # MoE
    TOPK = "topk"
    GROUP_BY = "group_by"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    CACHE = "cache"
    EXPERTS = "experts"
    EXPERT_SHARE = "expert_share"
    # a residual path of several streams (manifold-constrained
    # hyper-connections): the mixing around a block
    HYPER_CONNECTION = "hyper_connection"
    # fused
    FUSED = "fused"
    # parallel ops (first-class PCG nodes, SURVEY.md §2.3)
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    ALL_TO_ALL = "all_to_all"
    FUSED_PARALLEL = "fused_parallel"
    PIPELINE = "pipeline"
    # loss/metrics pseudo-ops
    LOSS = "loss"
    METRICS = "metrics"


# Ops whose lowering is a pure resharding (no math).
PARALLEL_OP_TYPES = frozenset(
    {
        OpType.REPARTITION,
        OpType.COMBINE,
        OpType.REPLICATE,
        OpType.REDUCTION,
        OpType.ALL_TO_ALL,
        OpType.FUSED_PARALLEL,
        # NOTE: PIPELINE is NOT here — it was a stub enum in the reference
        # but is a real compute composite in this framework (ops/attrs.py
        # PipelineAttrs), priced like any op plus bubble/ppermute terms.
    }
)
