"""Attribute dataclasses for every operator (shape inference + weights + FLOPs).

Covers the reference op inventory (SURVEY.md §2.2, src/ops/*) plus TPU-native
additions (RMSNorm, RingAttention). Shapes are numpy-ordered (dim 0 = batch);
degree/axes of sharded dims propagate through inference wherever an output
dim corresponds one-to-one to an input dim (the role of the reference's
ParallelDimMappingRecords, operator.h:22-49).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

from flexflow_tpu.ffconst import ActiMode, AggrMode, DataType, PoolType
from flexflow_tpu.ops.base import (
    OpAttrs,
    WeightSpec,
    broadcast_dims,
    elementwise_like,
    fresh,
)
from flexflow_tpu.pcg.tensor import ParallelDim, ParallelTensorShape, TensorShape

Shape = ParallelTensorShape


def _carry(dim: ParallelDim, size: Optional[int] = None) -> ParallelDim:
    """Copy a dim's sharding onto a (possibly resized) output dim; drops the
    sharding if the new size is not divisible by the degree."""
    size = dim.size if size is None else size
    if size % dim.degree == 0:
        return ParallelDim(size, dim.degree, dim.axes)
    return ParallelDim(size)


# ---------------------------------------------------------------------------
# sources


@dataclasses.dataclass(frozen=True)
class InputAttrs(OpAttrs):
    """PCG source node for a user input (reference NoOp/Input, noop.cc)."""

    shape: TensorShape

    def infer(self, *ins):
        return (ParallelTensorShape.from_shape(self.shape),)


@dataclasses.dataclass(frozen=True)
class WeightAttrs(OpAttrs):
    """PCG source node for a standalone weight (reference create_weight)."""

    shape: TensorShape
    initializer: str = "glorot_uniform"

    def infer(self, *ins):
        return (ParallelTensorShape.from_shape(self.shape),)

    def weights(self, *ins):
        return {"weight": WeightSpec(self.shape, self.initializer)}


@dataclasses.dataclass(frozen=True)
class NoOpAttrs(OpAttrs):
    def infer(self, *ins):
        return (elementwise_like(ins[0]),)


# ---------------------------------------------------------------------------
# dense / conv / embedding


@dataclasses.dataclass(frozen=True)
class LinearAttrs(OpAttrs):
    """Dense layer (reference src/ops/linear.cc): y = act(x @ W + b).

    x: (..., in_dim) -> y: (..., out_dim); W: (in_dim, out_dim), b: (out_dim,).
    Parallelizable on batch dims (data), out_dim (parameter/TP column), and
    in_dim with a Reduction afterwards (TP row) — the degree mappings the
    reference builds in LinearParams::construct_mappings (linear.cc:1095).
    """

    out_dim: int
    use_bias: bool = True
    activation: ActiMode = ActiMode.NONE
    dtype: Optional[DataType] = None

    def infer(self, x: Shape):
        out_dims = tuple(_carry(d) for d in x.dims[:-1]) + (ParallelDim(self.out_dim),)
        return (Shape(out_dims, self.dtype or x.dtype, x.replica),)

    def weights(self, x: Shape):
        in_dim = x.dims[-1].size
        w = {"kernel": WeightSpec(TensorShape((in_dim, self.out_dim), x.dtype))}
        if self.use_bias:
            w["bias"] = WeightSpec(TensorShape((self.out_dim,), x.dtype), "zeros")
        return w

    def flops(self, ins, outs):
        x = ins[0]
        batch = math.prod(d.size for d in x.dims[:-1])
        return 2 * batch * x.dims[-1].size * self.out_dim


@dataclasses.dataclass(frozen=True)
class Conv2DAttrs(OpAttrs):
    """2-D convolution, NCHW (reference src/ops/conv_2d.cc; lowered to
    lax.conv_general_dilated on TPU)."""

    out_channels: int
    kernel: Tuple[int, int]
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    groups: int = 1
    use_bias: bool = True
    activation: ActiMode = ActiMode.NONE

    def infer(self, x: Shape):
        n, c, h, w = (d.size for d in x.dims)
        oh = (h + 2 * self.padding[0] - self.kernel[0]) // self.stride[0] + 1
        ow = (w + 2 * self.padding[1] - self.kernel[1]) // self.stride[1] + 1
        dims = (
            _carry(x.dims[0]),
            ParallelDim(self.out_channels),
            ParallelDim(oh),
            ParallelDim(ow),
        )
        return (Shape(dims, x.dtype, x.replica),)

    def weights(self, x: Shape):
        cin = x.dims[1].size
        w = {
            "kernel": WeightSpec(
                TensorShape(
                    (self.out_channels, cin // self.groups, *self.kernel), x.dtype
                )
            )
        }
        if self.use_bias:
            w["bias"] = WeightSpec(TensorShape((self.out_channels,), x.dtype), "zeros")
        return w

    def flops(self, ins, outs):
        x, y = ins[0], outs[0]
        cin = x.dims[1].size
        per_out = 2 * cin // self.groups * self.kernel[0] * self.kernel[1]
        return per_out * y.to_shape().num_elements()


@dataclasses.dataclass(frozen=True)
class EmbeddingAttrs(OpAttrs):
    """Embedding lookup (reference src/ops/embedding.cc). Input int ids
    (batch, bag); NONE -> (batch, bag, out_dim); SUM/AVG pool the bag dim ->
    (batch, out_dim). With `emit_table` the node has a second output, the
    table itself (num_entries, out_dim), which a TIED head multiplies by
    (TiedHeadAttrs): the graph then holds the table as ONE leaf."""

    num_entries: int
    out_dim: int
    aggr: AggrMode = AggrMode.NONE
    dtype: DataType = DataType.FLOAT
    emit_table: bool = False

    def infer(self, x: Shape):
        if self.aggr == AggrMode.NONE:
            dims = tuple(_carry(d) for d in x.dims) + (ParallelDim(self.out_dim),)
        else:
            dims = tuple(_carry(d) for d in x.dims[:-1]) + (ParallelDim(self.out_dim),)
        out = Shape(dims, self.dtype, x.replica)
        if not self.emit_table:
            return (out,)
        return (out, fresh((self.num_entries, self.out_dim), self.dtype))

    def weights(self, x: Shape):
        return {
            "kernel": WeightSpec(
                TensorShape((self.num_entries, self.out_dim), self.dtype), "normal"
            )
        }

    def flops(self, ins, outs):
        return outs[0].to_shape().num_elements()


@dataclasses.dataclass(frozen=True)
class TiedHeadAttrs(OpAttrs):
    """The output head of a model whose head IS its embedding's table
    (`tie_word_embeddings`): logits = (h E^T) * `scale`, on inputs h
    (batch, seq, dim) and the table (entries, dim), the second output of
    an embedding built with `emit_table`. No weights of its own."""

    scale: float = 1.0

    def infer(self, h: Shape, table: Shape):
        if h.dims[-1].size != table.dims[-1].size:
            raise ValueError(f"tied head: {h} against a table {table}")
        dims = tuple(_carry(d) for d in h.dims[:-1]) + (
            ParallelDim(table.dims[0].size),)
        return (Shape(dims, h.dtype, h.replica),)

    def flops(self, ins, outs):
        return 2 * outs[0].to_shape().num_elements() * ins[0].dims[-1].size


@dataclasses.dataclass(frozen=True)
class BatchMatmulAttrs(OpAttrs):
    """(b..., m, k) @ (b..., k, n) (reference src/ops/batch_matmul.cc).
    a_seq_length_dim/b_seq_length_dim support iteration-config truncation."""

    a_seq_length_dim: int = -1
    b_seq_length_dim: int = -1

    def infer(self, a: Shape, b: Shape):
        if a.ndim != b.ndim or a.ndim < 2:
            raise ValueError(f"batch_matmul rank mismatch: {a} vs {b}")
        if a.dims[-1].size != b.dims[-2].size:
            raise ValueError(f"batch_matmul inner dim mismatch: {a} vs {b}")
        dims = tuple(_carry(d) for d in a.dims[:-1]) + (_carry(b.dims[-1]),)
        return (Shape(dims, a.dtype, a.replica),)

    def flops(self, ins, outs):
        a, b = ins
        batch = math.prod(d.size for d in a.dims[:-2])
        return 2 * batch * a.dims[-2].size * a.dims[-1].size * b.dims[-1].size


# ---------------------------------------------------------------------------
# recurrent


@dataclasses.dataclass(frozen=True)
class LSTMAttrs(OpAttrs):
    """Single-layer LSTM over a full sequence (capability analog of the
    reference's legacy NMT LSTM node, nmt/rnn.h:161 add_lstm_node — which
    unrolls one CUDA node per LSTM_PER_NODE_LENGTH timesteps; on TPU the
    whole sequence is one lax.scan with the input projection hoisted into a
    single MXU matmul).

    Inputs: x (batch, seq, in_dim) [, h0 (batch, hidden), c0 (batch, hidden)].
    Outputs: y (batch, seq, hidden), h_n (batch, hidden), c_n (batch, hidden).
    Gate order i,f,g,o matches torch.nn.LSTM's weight layout (wx/wh are its
    weight_ih/weight_hh transposed, bias = b_ih + b_hh). Batch dim shards on
    the data axis; the sequence dim is the recurrence and never shards.
    """

    hidden: int
    use_bias: bool = True
    reverse: bool = False

    def infer(self, x: Shape, h0: Optional[Shape] = None,
              c0: Optional[Shape] = None):
        if x.ndim != 3:
            raise ValueError(f"lstm expects (batch, seq, in_dim), got {x}")
        for nm, st in (("h0", h0), ("c0", c0)):
            if st is None:
                continue
            if st.ndim != 2 or st.dims[0].size != x.dims[0].size \
                    or st.dims[1].size != self.hidden:
                raise ValueError(
                    f"lstm initial state {nm} must be (batch={x.dims[0].size},"
                    f" hidden={self.hidden}), got {st}"
                )
        b, s = x.dims[0], x.dims[1]
        h = ParallelDim(self.hidden)
        y = Shape((_carry(b), ParallelDim(s.size), h), x.dtype, x.replica)
        state = Shape((_carry(b), h), x.dtype, x.replica)
        return (y, state, state)

    def weights(self, x: Shape, *state):
        in_dim = x.dims[-1].size
        w = {
            "wx": WeightSpec(TensorShape((in_dim, 4 * self.hidden), x.dtype)),
            "wh": WeightSpec(TensorShape((self.hidden, 4 * self.hidden), x.dtype)),
        }
        if self.use_bias:
            w["bias"] = WeightSpec(TensorShape((4 * self.hidden,), x.dtype), "zeros")
        return w

    def flops(self, ins, outs):
        x = ins[0]
        b, s, d = (dim.size for dim in x.dims)
        return 2 * b * s * 4 * self.hidden * (d + self.hidden)


# ---------------------------------------------------------------------------
# attention


@dataclasses.dataclass(frozen=True)
class MultiHeadAttentionAttrs(OpAttrs):
    """Multi-head attention (reference src/ops/attention.cc — cuDNN
    multiHeadAttn; here lowered to fused einsum/flash attention).

    Inputs q, k, v: (batch, seq, embed). Weights packed per-head like the
    reference's {num_heads, qkvo} layout so head-parallelism ("attribute
    parallelism", attention.cc:210-230) shards one weight dim.
    GQA (kv_heads < num_heads) and causal masking are TPU-native extensions
    needed for the Llama family.
    """

    embed_dim: int
    num_heads: int
    kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    causal: bool = False
    use_bias: bool = False
    dropout: float = 0.0
    # rotary position embeddings (TPU-native addition for the Llama family)
    rope: bool = False
    rope_theta: float = 10000.0
    # sliding window: key j is visible to query i iff j <= i and
    # i - j < window (the query itself and the window - 1 rows before
    # it). None is full attention. A model may mix both by layer.
    window: Optional[int] = None
    # YaRN on the rope's frequencies, (factor, original_max, beta_fast,
    # beta_slow, attention_factor): `yarn_inv_freq` blends theta^(-2i/d)
    # with the same over `factor` between the two correction dims, and
    # cos and sin are both multiplied by attention_factor. None: plain.
    rope_scaling: Optional[Tuple[float, int, float, float, float]] = None
    # what the scores are multiplied by; None: kdim ** -0.5 (Granite 4.0's
    # `attention_multiplier` is 1/64 on heads of 64, not 1/8)
    softmax_scale: Optional[float] = None

    def __post_init__(self):
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.window is not None and not self.causal:
            raise ValueError("a sliding window is causal: pass causal=True")
        if self.rope_scaling is not None and not self.rope:
            raise ValueError("rope_scaling needs rope=True")

    @property
    def kdim(self) -> int:
        return self.head_dim or self.embed_dim // self.num_heads

    @property
    def num_kv(self) -> int:
        return self.kv_heads or self.num_heads

    @property
    def scale(self) -> float:
        if self.softmax_scale is not None:
            return self.softmax_scale
        return 1.0 / (self.kdim**0.5)

    def infer(self, q: Shape, k: Shape = None, v: Shape = None):
        dims = tuple(_carry(d) for d in q.dims[:-1]) + (ParallelDim(self.embed_dim),)
        return (Shape(dims, q.dtype, q.replica),)

    def weights(self, q: Shape, k: Shape = None, v: Shape = None):
        k = k or q
        v = v or q
        dt = q.dtype
        hd = self.kdim
        w = {
            "wq": WeightSpec(TensorShape((q.dims[-1].size, self.num_heads, hd), dt)),
            "wk": WeightSpec(TensorShape((k.dims[-1].size, self.num_kv, hd), dt)),
            "wv": WeightSpec(TensorShape((v.dims[-1].size, self.num_kv, hd), dt)),
            "wo": WeightSpec(TensorShape((self.num_heads, hd, self.embed_dim), dt)),
        }
        if self.use_bias:
            w["bq"] = WeightSpec(TensorShape((self.num_heads, hd), dt), "zeros")
            w["bk"] = WeightSpec(TensorShape((self.num_kv, hd), dt), "zeros")
            w["bv"] = WeightSpec(TensorShape((self.num_kv, hd), dt), "zeros")
            w["bo"] = WeightSpec(TensorShape((self.embed_dim,), dt), "zeros")
        return w

    def flops(self, ins, outs):
        q = ins[0]
        b = q.dims[0].size
        s = q.dims[1].size
        e = q.dims[-1].size
        hd = self.kdim
        proj = 2 * b * s * e * (self.num_heads + 2 * self.num_kv + self.num_heads) * hd
        attn = 2 * 2 * b * self.num_heads * s * s * hd
        return proj + attn


@dataclasses.dataclass(frozen=True)
class RingAttentionAttrs(MultiHeadAttentionAttrs):
    """Sequence-parallel attention (net-new vs reference, SURVEY §5.7):
    identical math to MultiHeadAttention with the sequence dim sharded over
    a mesh axis. `seq_mode` picks the exchange pattern:
      - "ring":    k/v blocks rotate via ppermute, blockwise online softmax
                   overlapping compute with ICI transfer;
      - "ulysses": one all-to-all turns seq sharding into head sharding,
                   full attention runs locally, a second all-to-all turns
                   it back (DeepSpeed-Ulysses pattern)."""

    seq_mode: str = "ring"


@dataclasses.dataclass(frozen=True)
class LatentAttentionAttrs(OpAttrs):
    """Multi-head LATENT attention (MLA, the DeepSeek-V2/V3 block that
    Mistral-Small-4 publishes): queries and keys/values go through
    low-rank projections, a head is split into a part without rope and a
    part with it, and one roped key part `k_r` is shared by all heads.

        c_q = RMSNorm(x W_dq)                q_h = [q_nope_h | q_rope_h] = c_q W_uq
        [c_kv | k_r] = x W_dkv;  c_kv <- RMSNorm(c_kv)
        [k_nope_h | v_h] = c_kv W_ukv
        s_h = (q_nope_h . k_nope_h + rope(q_rope_h) . rope(k_r)) * softmax_scale

    An op of its own and not an extension of MultiHeadAttentionAttrs:
    that op has ONE head size for q, k and v and caches per-head K and V,
    and every consumer of it (search rules, TP views, the dense decode
    cache, the int8 sidecar) reads `kdim` / `num_kv` in that sense. What
    a latent layer caches is one row a token, `[c_kv | k_r]`
    (`latent_width` values), whatever the head count; the paged lowering
    attends in the absorbed form and never materialises per-head K/V
    (paged/latent.py).

    Rope is YaRN's: frequencies blended between `theta^(-2i/d)` and the
    same over `rope_factor` by the linear ramp between the correction
    dims of `beta_fast` / `beta_slow`; pairs are interleaved (2i, 2i+1)
    when `rope_interleave`. `softmax_scale` is the whole score scale
    (head size and YaRN's mscale^2 folded in by the builder) and
    `q_scale_beta` > 0 multiplies q by 1 + beta * ln(1 + floor(pos /
    rope_original_max)) (the Llama-4 position scale).

    `q_lora_rank` None (Ling-3.0-flash) drops the query's low-rank step:
    q_h = x W_uq, W_uq (embed, heads, nope + rope), no W_dq and no norm.
    `out_gate` multiplies each head's output by sigmoid(x w_gate,h), one
    scalar a head, before W_o. `qk_rope_head_dim` 0 (GLM-5.3-Flash) is a
    head without a rope part: no `k_r`, the cached row is `c_kv` alone.

    `index_heads` > 0 makes the layer SPARSE (DeepSeek sparse attention
    with pooled keys, GLM-5.3-Flash): an indexer of its own chooses, a
    query, the blocks of `index_pool` tokens it attends to,

        qI_i = c_q W_iq,i (i < index_heads)   kI = LayerNorm(x W_ik)
        w = x W_w * index_heads^-1/2 * index_dim^-1/2
        rope (interleaved pairs, `index_rope_theta`) on the first
        `index_rope_dim` values of qI_i and kI
        kP_b = mean of kI over block b's `index_pool` tokens (whole blocks)
        I_{t,b} = sum_i w_{t,i} ReLU(qI_{t,i} . kP_b)   for b < t // index_pool
        B_t = {t // index_pool} + the index_topk / index_pool - 1 blocks of
              largest I_{t,b} (all where fewer; a tie to the lower b)

    and the softmax runs over the visible tokens of B_t's blocks alone. The
    indexer is part of THIS op and not an op beside it: it reads the op's
    own `c_q`, and what it caches (one pooled key a block, entry "kp" of
    the node's pool dict, `index_pool_specs`) has to live on the pages of
    the op's latent rows so that one page table, one preemption and one
    defragmentation move both. Absent (`index_heads` 0) the op is the
    dense latent layer it was, bit for bit. A context of at most
    `index_topk` tokens selects everything."""

    embed_dim: int
    num_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: float
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_interleave: bool = True
    q_scale_beta: float = 0.0
    out_gate: bool = False
    index_heads: int = 0        # 0: no indexer, the dense latent layer
    index_dim: int = 128
    index_topk: int = 2048      # TOKENS a query attends to, its own block's too
    index_pool: int = 4         # tokens a pooled key
    index_rope_dim: int = 64
    index_rope_theta: float = 1e6

    def __post_init__(self):
        if self.index_heads:
            if self.q_lora_rank is None:
                raise ValueError("the indexer's queries come from c_q: a "
                                 "sparse latent layer needs q_lora_rank")
            if (self.index_topk % self.index_pool
                    or self.index_topk < 2 * self.index_pool):
                raise ValueError(
                    f"index_topk {self.index_topk} counts tokens: a multiple "
                    f"of index_pool {self.index_pool}, two blocks or more")
            if not 0 <= self.index_rope_dim <= self.index_dim \
                    or self.index_rope_dim % 2:
                raise ValueError("index_rope_dim: an even part of index_dim")

    @property
    def latent_width(self) -> int:
        """Values a token's cache row holds: c_kv then k_r."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def index_blocks(self) -> int:
        """Whole blocks a query chooses beside its own."""
        return self.index_topk // self.index_pool - 1

    def index_pool_specs(self, num_pages: int, page_size: int):
        """(shape, lanes) of the pooled keys' pool entry, or None: one row
        of `index_dim` values a block of `index_pool` tokens, on the page
        of those tokens."""
        if not self.index_heads:
            return None
        if page_size % self.index_pool:
            raise ValueError(
                f"page_size {page_size} is not a multiple of index_pool "
                f"{self.index_pool}: a block of pooled tokens may not "
                "straddle two pages")
        return (num_pages, page_size // self.index_pool, self.index_dim)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def infer(self, x: Shape):
        dims = tuple(_carry(d) for d in x.dims[:-1]) + (
            ParallelDim(self.embed_dim),)
        return (Shape(dims, x.dtype, x.replica),)

    def weights(self, x: Shape):
        dt = x.dtype
        e, h = x.dims[-1].size, self.num_heads
        q_in = e if self.q_lora_rank is None else self.q_lora_rank
        low_rank = {} if self.q_lora_rank is None else {
            "w_dq": WeightSpec(TensorShape((e, self.q_lora_rank), dt)),
            "q_norm": WeightSpec(TensorShape((self.q_lora_rank,), dt),
                                 "ones"),
        }
        gate = {"w_gate": WeightSpec(TensorShape((e, h), dt))} \
            if self.out_gate else {}
        indexer = {} if not self.index_heads else {
            "w_iq": WeightSpec(TensorShape(
                (self.q_lora_rank, self.index_heads, self.index_dim), dt)),
            "w_ik": WeightSpec(TensorShape((e, self.index_dim), dt)),
            "ik_scale": WeightSpec(TensorShape((self.index_dim,), dt),
                                   "ones"),
            "ik_bias": WeightSpec(TensorShape((self.index_dim,), dt),
                                  "zeros"),
            "w_iw": WeightSpec(TensorShape((e, self.index_heads), dt)),
        }
        return {
            **low_rank,
            "w_uq": WeightSpec(TensorShape(
                (q_in, h, self.qk_head_dim), dt)),
            "w_dkv": WeightSpec(TensorShape((e, self.latent_width), dt)),
            "kv_norm": WeightSpec(TensorShape((self.kv_lora_rank,), dt),
                                  "ones"),
            "w_ukv": WeightSpec(TensorShape(
                (self.kv_lora_rank, h,
                 self.qk_nope_head_dim + self.v_head_dim), dt)),
            "wo": WeightSpec(TensorShape((h, self.v_head_dim,
                                          self.embed_dim), dt)),
            **gate,
            **indexer,
        }

    def flops(self, ins, outs):
        x = ins[0]
        b, s, e = x.dims[0].size, x.dims[1].size, x.dims[-1].size
        h = self.num_heads
        q_proj = (e * h * self.qk_head_dim if self.q_lora_rank is None
                  else e * self.q_lora_rank
                  + self.q_lora_rank * h * self.qk_head_dim)
        proj = 2 * b * s * (
            q_proj + (e * h if self.out_gate else 0)
            + e * self.latent_width
            + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                       + self.v_head_dim)
            + h * self.v_head_dim * e)
        attn = 2 * b * h * s * s * (self.qk_head_dim + self.v_head_dim)
        return proj + attn


@dataclasses.dataclass(frozen=True)
class KdaAttentionAttrs(OpAttrs):
    """A delta-rule LINEAR attention layer with per-channel decay (KDA,
    Kimi Linear, arXiv:2510.26692; the linear layers of Ling-3.0-flash).
    `num_heads` heads of d = `head_dim` key and value channels:

        q~, k~, v~ = x W_q, x W_k, x W_v; a causal depthwise convolution of
        `conv_taps` taps over time on each channel, then SiLU;
        q = l2norm(q) d^-1/2, k = l2norm(k) a head
        a_t = lower_bound * sigmoid(exp(A_log_h) (x W_f + dt_bias))   (< 0)
        beta_t = sigmoid(x w_beta,h)
        S_t = (I - beta_t k_t k_t^T) Diag(exp a_t) S_{t-1} + beta_t k_t v_t^T
        y = [RMSNorm_d(S_t^T q_t) * sigmoid(x W_g)_h] W_o

    What it keeps of the past is a FIXED-SIZE state, whatever the length:
    S (heads, d, d) float32 and the convolution's last `conv_taps` - 1
    input rows. A paged server holds one of each a SLOT, beside its pages
    (runtime/executor.py `paged_kv_cache_specs`; ops/kda_attention.py has
    the lowerings, ops/pallas/kda_scan.py the kernel)."""

    embed_dim: int
    num_heads: int
    head_dim: int
    conv_taps: int = 4
    lower_bound: float = -5.0
    norm_eps: float = 1e-6
    # None: W_f and W_g are full rank (Ling-3.0-flash, `no_kda_lora`);
    # r: each goes through rank r, x W_fa W_fb and x W_ga W_gb (Kimi
    # Linear's layout, GLM-5.3-Flash)
    gate_rank: Optional[int] = None

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    def infer(self, x: Shape):
        dims = tuple(_carry(d) for d in x.dims[:-1]) + (
            ParallelDim(self.embed_dim),)
        return (Shape(dims, x.dtype, x.replica),)

    def weights(self, x: Shape):
        dt = x.dtype
        e, h, c = x.dims[-1].size, self.num_heads, self.inner

        def mat(*shape, init="glorot_uniform"):
            return WeightSpec(TensorShape(shape, dt), init)

        r = self.gate_rank
        w_f = ({"w_f": mat(e, c)} if r is None else
               {"w_fa": mat(e, r), "w_fb": mat(r, c)})
        w_g = ({"w_g": mat(e, c)} if r is None else
               {"w_ga": mat(e, r), "w_gb": mat(r, c)})
        return {
            "wq": mat(e, c), "wk": mat(e, c), "wv": mat(e, c),
            "conv_q": mat(self.conv_taps, c), "conv_k": mat(self.conv_taps, c),
            "conv_v": mat(self.conv_taps, c),
            **w_f, "dt_bias": mat(c, init="zeros"),
            "a_log": mat(h, init="zeros"), "w_beta": mat(e, h),
            **w_g, "o_norm": mat(self.head_dim, init="ones"),
            "wo": mat(c, e),
        }

    def state_specs(self, slots: int):
        """{name: (shape, dtype name or None: the activations')} of what a
        server keeps a slot."""
        return {
            "s": ((slots, self.num_heads, self.head_dim, self.head_dim),
                  "float32"),
            "conv": ((slots, self.conv_taps - 1, 3 * self.inner), None),
        }

    def flops(self, ins, outs):
        x = ins[0]
        b, s, e = x.dims[0].size, x.dims[1].size, x.dims[-1].size
        c = self.inner
        gates = (2 * e * c if self.gate_rank is None
                 else 2 * self.gate_rank * (e + c))
        proj = 2 * b * s * (e * (3 * c + self.num_heads) + gates + c * e)
        return proj + 7 * b * s * c * self.head_dim


@dataclasses.dataclass(frozen=True)
class Mamba2Attrs(OpAttrs):
    """A Mamba-2 STATE-SPACE mixer (arXiv:2405.21060; the `mamba` layers of
    Granite 4.0-H): `num_heads` heads of P = `head_dim` channels, a state
    of N = `state_dim` a channel, ONE group (B and C are shared by the
    heads), a SCALAR decay a head and token:

        [z | xBC | dt] = u W_in       (H P | H P + 2 N | H, no bias)
        xBC = silu(conv(xBC) + bias)  depthwise, causal, `conv_taps` taps
        x (H, P), B (N), C (N) = split(xBC)
        D_t,h = softplus(dt_t,h + dt_bias_h);  a_t,h = -exp(A_log_h) D_t,h
        S_t,h = exp(a_t,h) S_t-1,h + D_t,h x_t,h B_t^T          (P x N)
        y_t,h = S_t,h C_t + Dskip_h x_t,h
        out = [RMSNorm_HP(y * silu(z)) * w] W_out   (the gate BEFORE the
        norm, one norm over all H P channels)

    What it keeps of the past is a FIXED-SIZE state, whatever the length:
    S (heads, P, N) float32 and the convolution's last `conv_taps` - 1
    input rows of xBC. A paged server holds one of each a SLOT, beside its
    pages, as it does for a KDA layer (ops/mamba2.py has the lowerings,
    ops/pallas/ssd_scan.py the kernel)."""

    embed_dim: int
    num_heads: int
    head_dim: int
    state_dim: int
    conv_taps: int = 4
    norm_eps: float = 1e-5

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.state_dim

    def infer(self, x: Shape):
        dims = tuple(_carry(d) for d in x.dims[:-1]) + (
            ParallelDim(self.embed_dim),)
        return (Shape(dims, x.dtype, x.replica),)

    def weights(self, x: Shape):
        dt = x.dtype
        e, h, c = x.dims[-1].size, self.num_heads, self.conv_dim

        def mat(*shape, init="glorot_uniform"):
            return WeightSpec(TensorShape(shape, dt), init)

        return {
            "w_in": mat(e, self.inner + c + h),
            "conv": mat(self.conv_taps, c), "conv_bias": mat(c, init="zeros"),
            "dt_bias": mat(h, init="zeros"), "a_log": mat(h, init="zeros"),
            "d_skip": mat(h, init="ones"),
            "norm": mat(self.inner, init="ones"),
            "w_out": mat(self.inner, self.embed_dim),
        }

    def state_specs(self, slots: int):
        """{name: (shape, dtype name or None: the activations')} of what a
        server keeps a slot."""
        return {
            "s": ((slots, self.num_heads, self.head_dim, self.state_dim),
                  "float32"),
            "conv": ((slots, self.conv_taps - 1, self.conv_dim), None),
        }

    def flops(self, ins, outs):
        x = ins[0]
        b, s, e = x.dims[0].size, x.dims[1].size, x.dims[-1].size
        proj = 2 * b * s * e * (2 * self.inner + self.conv_dim
                                + self.num_heads)
        return proj + 5 * b * s * self.inner * self.state_dim


# ---------------------------------------------------------------------------
# elementwise


@dataclasses.dataclass(frozen=True)
class ElementBinaryAttrs(OpAttrs):
    """add/sub/mul/div/max/min with numpy broadcast (reference
    src/ops/element_binary.cc)."""

    kind: str  # add|subtract|multiply|divide|max|min
    # marks an add of an absolute-position row table (GPT-2/BERT learned
    # positions): under KV-cache decode the lowering takes the table rows
    # at the cache position, and generate() guards total length against
    # the table size — an explicit graph property, not a shape heuristic
    position_table: bool = False

    def infer(self, a: Shape, b: Shape):
        out = broadcast_dims(
            tuple(d.size for d in a.dims), tuple(d.size for d in b.dims)
        )
        src = a if a.ndim >= b.ndim else b
        dims = []
        for i, size in enumerate(out):
            sd = src.dims[i]
            dims.append(_carry(sd, size) if sd.size == size else ParallelDim(size))
        return (Shape(tuple(dims), a.dtype, src.replica),)

    def flops(self, ins, outs):
        return outs[0].to_shape().num_elements()


@dataclasses.dataclass(frozen=True)
class ElementUnaryAttrs(OpAttrs):
    """exp/sin/cos/relu/gelu/sigmoid/tanh/elu/rsqrt/pow/identity and
    scalar_{add,sub,multiply,truediv} (reference src/ops/element_unary.cc);
    `scalar` feeds pow exponent / scalar operand."""

    kind: str
    scalar: float = 0.0
    inplace: bool = False

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def flops(self, ins, outs):
        return outs[0].to_shape().num_elements()


# ---------------------------------------------------------------------------
# shape ops


@dataclasses.dataclass(frozen=True)
class ReshapeAttrs(OpAttrs):
    shape: Tuple[int, ...]

    def infer(self, x: Shape):
        if math.prod(self.shape) != x.to_shape().num_elements():
            raise ValueError(f"reshape {x} -> {self.shape}: element count mismatch")
        return (fresh(self.shape, x.dtype),)


@dataclasses.dataclass(frozen=True)
class FlatAttrs(OpAttrs):
    """Flatten all non-batch dims (reference src/ops/flat.cc)."""

    def infer(self, x: Shape):
        rest = math.prod(d.size for d in x.dims[1:])
        return (Shape((_carry(x.dims[0]), ParallelDim(rest)), x.dtype, x.replica),)


@dataclasses.dataclass(frozen=True)
class TransposeAttrs(OpAttrs):
    perm: Tuple[int, ...]

    def infer(self, x: Shape):
        dims = tuple(_carry(x.dims[p]) for p in self.perm)
        return (Shape(dims, x.dtype, x.replica),)


@dataclasses.dataclass(frozen=True)
class ReverseAttrs(OpAttrs):
    axis: int

    def infer(self, x: Shape):
        return (elementwise_like(x),)


@dataclasses.dataclass(frozen=True)
class ConcatAttrs(OpAttrs):
    axis: int

    def infer(self, *ins: Shape):
        ax = self.axis % ins[0].ndim
        total = sum(s.dims[ax].size for s in ins)
        dims = []
        for i, d in enumerate(ins[0].dims):
            dims.append(ParallelDim(total) if i == ax else _carry(d))
        return (Shape(tuple(dims), ins[0].dtype, ins[0].replica),)


@dataclasses.dataclass(frozen=True)
class SplitAttrs(OpAttrs):
    sizes: Tuple[int, ...]
    axis: int

    def infer(self, x: Shape):
        ax = self.axis % x.ndim
        outs = []
        for sz in self.sizes:
            dims = tuple(
                ParallelDim(sz) if i == ax else _carry(d)
                for i, d in enumerate(x.dims)
            )
            outs.append(Shape(dims, x.dtype, x.replica))
        return tuple(outs)


@dataclasses.dataclass(frozen=True)
class CastAttrs(OpAttrs):
    dtype: DataType

    def infer(self, x: Shape):
        return (elementwise_like(x, self.dtype),)


# ---------------------------------------------------------------------------
# norm / pooling / softmax / dropout


@dataclasses.dataclass(frozen=True)
class Pool2DAttrs(OpAttrs):
    kernel: Tuple[int, int]
    stride: Tuple[int, int]
    padding: Tuple[int, int] = (0, 0)
    pool_type: PoolType = PoolType.MAX
    activation: ActiMode = ActiMode.NONE

    def infer(self, x: Shape):
        n, c, h, w = (d.size for d in x.dims)
        oh = (h + 2 * self.padding[0] - self.kernel[0]) // self.stride[0] + 1
        ow = (w + 2 * self.padding[1] - self.kernel[1]) // self.stride[1] + 1
        dims = (_carry(x.dims[0]), _carry(x.dims[1]), ParallelDim(oh), ParallelDim(ow))
        return (Shape(dims, x.dtype, x.replica),)


@dataclasses.dataclass(frozen=True)
class BatchNormAttrs(OpAttrs):
    """BatchNorm over the channel dim of NCHW (reference src/ops/batch_norm.cc).
    Running stats are non-trainable weights updated by the train step."""

    relu: bool = False
    momentum: float = 0.1
    eps: float = 1e-5

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        c = TensorShape((x.dims[1].size,), x.dtype)
        return {
            "scale": WeightSpec(c, "ones"),
            "bias": WeightSpec(c, "zeros"),
            "running_mean": WeightSpec(c, "zeros", trainable=False),
            "running_var": WeightSpec(c, "ones", trainable=False),
        }


@dataclasses.dataclass(frozen=True)
class LayerNormAttrs(OpAttrs):
    """LayerNorm over trailing axes (reference src/ops/layer_norm.cc)."""

    axes: Tuple[int, ...] = (-1,)
    elementwise_affine: bool = True
    eps: float = 1e-5

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        if not self.elementwise_affine:
            return {}
        norm_shape = tuple(x.dims[a].size for a in self.axes)
        return {
            "scale": WeightSpec(TensorShape(norm_shape, x.dtype), "ones"),
            "bias": WeightSpec(TensorShape(norm_shape, x.dtype), "zeros"),
        }


@dataclasses.dataclass(frozen=True)
class RMSNormAttrs(OpAttrs):
    """RMSNorm (TPU-native addition for the Llama family)."""

    eps: float = 1e-6

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        return {"scale": WeightSpec(TensorShape((x.dims[-1].size,), x.dtype), "ones")}


@dataclasses.dataclass(frozen=True)
class SoftmaxAttrs(OpAttrs):
    axis: int = -1

    def infer(self, x: Shape):
        return (elementwise_like(x),)


@dataclasses.dataclass(frozen=True)
class DropoutAttrs(OpAttrs):
    rate: float
    seed: int = 0

    def infer(self, x: Shape):
        return (elementwise_like(x),)


# ---------------------------------------------------------------------------
# gather / reduce / topk


@dataclasses.dataclass(frozen=True)
class GatherAttrs(OpAttrs):
    """torch.gather semantics along `axis` (reference src/ops/gather.cc)."""

    axis: int

    def infer(self, x: Shape, index: Shape):
        return (Shape(tuple(_carry(d) for d in index.dims), x.dtype, x.replica),)


@dataclasses.dataclass(frozen=True)
class ReduceAttrs(OpAttrs):
    """reduce_sum / mean over axes (reference src/ops/reduce.cc, mean.cc)."""

    kind: str  # sum|mean
    axes: Tuple[int, ...]
    keepdims: bool = False

    def infer(self, x: Shape):
        for a in self.axes:
            # modulo would silently reduce the WRONG axis on out-of-range
            # input (axis 7 of a 2-D tensor -> axis 1)
            if not -x.ndim <= a < x.ndim:
                raise ValueError(
                    f"reduce axis {a} out of range for {x.ndim}-D input")
        ax = {a % x.ndim for a in self.axes}
        dims = []
        for i, d in enumerate(x.dims):
            if i in ax:
                if self.keepdims:
                    dims.append(ParallelDim(1))
            else:
                dims.append(_carry(d))
        return (Shape(tuple(dims), x.dtype, x.replica),)


@dataclasses.dataclass(frozen=True)
class TopKAttrs(OpAttrs):
    """Top-k along the last dim -> (values, indices) (reference src/ops/topk.cc)."""

    k: int
    sorted: bool = True

    def infer(self, x: Shape):
        dims = tuple(_carry(d) for d in x.dims[:-1]) + (ParallelDim(self.k),)
        return (
            Shape(dims, x.dtype, x.replica),
            Shape(dims, DataType.INT32, x.replica),
        )


# ---------------------------------------------------------------------------
# MoE ops


@dataclasses.dataclass(frozen=True)
class GroupByAttrs(OpAttrs):
    """Route tokens to per-expert buffers (reference src/ops/group_by.cc).

    Inputs: data (batch, dim), assignments (batch, k) int. Outputs: n_experts
    tensors (capacity, dim) where capacity = ceil(k*batch*alpha/n) — dense,
    capacity-dropped dispatch (TPU-native: one-hot matmul, no scatter).
    """

    n_experts: int
    alpha: float = 1.0  # capacity factor

    def capacity(self, batch: int, k: int) -> int:
        return max(1, int(math.ceil(k * batch * self.alpha / self.n_experts)))

    def infer(self, x: Shape, assign: Shape):
        batch = x.dims[0].size
        k = assign.dims[-1].size
        cap = self.capacity(batch, k)
        out = Shape((ParallelDim(cap), _carry(x.dims[-1])), x.dtype, x.replica)
        return tuple(out for _ in range(self.n_experts))


@dataclasses.dataclass(frozen=True)
class AggregateAttrs(OpAttrs):
    """Weighted combine of expert outputs (reference src/ops/aggregate.cc).

    Inputs: gate_preds (batch, k), gate_assign (batch, k), true_gate_assign
    (batch, k), gate gradients (batch, n), then n expert outputs (cap, dim).
    Output: (batch, dim). `lambda_bal` weighs the load-balancing gradient.
    """

    n_experts: int
    lambda_bal: float = 0.0

    def infer(self, *ins: Shape):
        gate_preds = ins[0]
        expert0 = ins[4]
        batch = gate_preds.dims[0].size
        dims = (_carry(gate_preds.dims[0], batch), _carry(expert0.dims[-1]))
        return (Shape(dims, expert0.dtype, expert0.replica),)


@dataclasses.dataclass(frozen=True)
class AggregateSpecAttrs(AggregateAttrs):
    """Speculative aggregate (reference src/ops/aggregate_spec.cc): outputs
    per-expert predictions stacked for replicated-label loss."""

    def infer(self, *ins: Shape):
        gate_preds = ins[0]
        expert0 = ins[4]
        batch = gate_preds.dims[0].size
        k = gate_preds.dims[-1].size
        dims = (ParallelDim(batch * k), _carry(expert0.dims[-1]))
        return (Shape(dims, expert0.dtype, expert0.replica),)


@dataclasses.dataclass(frozen=True)
class ExpertsAttrs(OpAttrs):
    """Fused expert-parallel FFN bank (TPU-native fusion of
    group_by -> per-expert dense stack -> aggregate into one op so the MoE
    hot path is a single einsum pair over an expert-sharded weight stack).

    Input: tokens (batch, dim), gate logits (batch, n_experts).
    Output: (batch, out_dim).
    """

    n_experts: int
    k: int
    hidden_dim: int
    out_dim: int
    alpha: float = 1.0
    activation: ActiMode = ActiMode.GELU
    lambda_bal: float = 1e-2
    # renormalize the top-k gate probs to sum 1 (Mixtral convention); False
    # matches the composite group_by/aggregate path, which combines with
    # raw softmax probs (reference aggregate.cc)
    normalize: bool = True
    # dispatch implementation: "sort" = token-sort + row scatter/gather
    # into a static (n*cap, d) buffer — O(tokens*dim) like the reference's
    # group_by.cu/aggregate.cu scatter kernels, the only design that
    # reaches Mixtral-scale shapes; "dense" = one-hot dispatch matmuls
    # (O(tokens*k*n*cap) fp32 mask) — kept as the numerics oracle
    dispatch: str = "sort"

    def capacity(self, batch: int) -> int:
        return max(1, int(math.ceil(self.k * batch * self.alpha / self.n_experts)))

    def infer(self, x: Shape, gate: Shape):
        dims = tuple(_carry(d) for d in x.dims[:-1]) + (ParallelDim(self.out_dim),)
        return (Shape(dims, x.dtype, x.replica),)

    def weights(self, x: Shape, gate: Shape):
        dim = x.dims[-1].size
        dt = x.dtype
        return {
            "w1": WeightSpec(TensorShape((self.n_experts, dim, self.hidden_dim), dt)),
            "w2": WeightSpec(
                TensorShape((self.n_experts, self.hidden_dim, self.out_dim), dt)
            ),
        }

    def flops(self, ins, outs):
        x = ins[0]
        tokens = math.prod(d.size for d in x.dims[:-1])
        dim = x.dims[-1].size
        return 2 * tokens * self.k * (dim * self.hidden_dim + self.hidden_dim * self.out_dim)


@dataclasses.dataclass(frozen=True)
class ExpertShareAttrs(OpAttrs):
    """One chip's SHARE of a dropless SwiGLU expert layer, with the
    layer's shared expert(s): the layer is told which experts it holds
    (`held_lo` <= e < `held_hi` of `n_experts`), routes every token over
    all `n_experts` (softmax in float32, top `k`, renormalised over
    those k when `norm_topk`, times `routed_scale`), and computes
    (`score` "sigmoid", `n_group` / `topk_group` and `select_bias` are the
    DeepSeek-V3 router Ling-3.0-flash publishes: sigmoid scores, a bias
    added for SELECTION only, a group's score the sum of its two largest,
    the `topk_group` best groups open, the top `k` within them, weights
    the unbiased scores; ops/expert_share.py `route`)

        y = sum over the token's top-k experts HELD HERE of
              w_e * (silu(x Wg_e) * x Wu_e) Wd_e   +   E_shared(x)

    What experts held elsewhere would add is left out: with the layer
    whole (`held` = all) this is the layer; with a share it is the
    partial sum an expert-parallel chip owns before its exchange, and
    the shares add up to the layer with the shared expert counted once
    (tests/test_mistral4.py). No token is ever dropped and no capacity
    exists, so a token's output never depends on its batch-mates; an
    expert no token reached is not read from HBM (the grouped kernel
    visits only groups with rows, ops/pallas/grouped_experts.py).
    Inputs: x (..., d). The router is a weight of the op (its logits
    are float32 whatever the activation dtype)."""

    n_experts: int
    k: int
    hidden_dim: int
    held_lo: int = 0
    held_hi: int = 0            # 0: all n_experts
    shared_hidden: int = 0      # 0: no shared expert
    norm_topk: bool = True
    routed_scale: float = 1.0
    score: str = "softmax"      # or "sigmoid"
    n_group: int = 1            # group-limited routing: of n_group groups
    topk_group: int = 1         #   of experts, the topk_group best open
    select_bias: bool = False   # a bias a routed expert, for selection only
    # L > 0 clamps every SwiGLU, routed and shared: silu(min(g, L)) *
    # clip(u, -L, L) (the gpt-oss form; GLM-5.3-Flash's `swiglu_limit`)
    swiglu_limit: float = 0.0

    @property
    def held(self) -> Tuple[int, int]:
        return (self.held_lo, self.held_hi or self.n_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held
        return hi - lo

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        d, dt, g, f = x.dims[-1].size, x.dtype, self.n_held, self.hidden_dim
        w = {
            "router": WeightSpec(TensorShape((d, self.n_experts), dt)),
            **({"bias": WeightSpec(TensorShape((self.n_experts,), dt),
                                   "zeros")} if self.select_bias else {}),
            "w_gate": WeightSpec(TensorShape((g, d, f), dt)),
            "w_up": WeightSpec(TensorShape((g, d, f), dt)),
            "w_down": WeightSpec(TensorShape((g, f, d), dt)),
        }
        if self.shared_hidden:
            fs = self.shared_hidden
            w["shared_gate"] = WeightSpec(TensorShape((d, fs), dt))
            w["shared_up"] = WeightSpec(TensorShape((d, fs), dt))
            w["shared_down"] = WeightSpec(TensorShape((fs, d), dt))
        return w

    def flops(self, ins, outs):
        x = ins[0]
        tokens = math.prod(d.size for d in x.dims[:-1])
        d = x.dims[-1].size
        routed = self.k * self.n_held / self.n_experts * self.hidden_dim
        return (2 * tokens * d * self.n_experts
                + 6 * tokens * d * (routed + self.shared_hidden))


@dataclasses.dataclass(frozen=True)
class HyperConnectionAttrs(OpAttrs):
    """The mixing of a residual path of `streams` = n streams around ONE
    block F (manifold-constrained hyper-connections, arXiv:2512.24880;
    GLM-5.3-Flash's `mhc`). A token's residual is X (n, C), carried
    through the graph as one row of n C values. Four parts, one a node:

      "expand"  x (.., C) -> X (.., n C): the embedding repeated n times
      "pre"     X -> (h (.., C), coef (.., n + n n) float32): with
                x~ = RMSNorm(vec X) (no learned scale) and the node's
                weights phi (n C, n + n + n n), b, alpha (3,),
                  Hpre  = sigmoid(alpha_0 x~ phi_pre + b_pre)        (n)
                  Hpost = 2 sigmoid(alpha_1 x~ phi_post + b_post)    (n)
                  Hres  = Sinkhorn_iters(exp(alpha_2 mat(x~ phi_res)
                          + b_res)): rows then columns divided by their
                          sum + `eps`, `sinkhorn_iters` times      (n, n)
                h = Hpre X is what the block reads; coef = [Hpost | Hres]
      "post"    (X, coef, y = F(h)) -> X' = Hres X + Hpost^T y
      "sum"     X -> the n streams added, before the final norm

    The coefficients are float32 whatever the activations. Every part is
    a function of one token's row alone, so the dense forward and a
    ragged serving launch share one lowering (ops/hyper_connection.py):
    caches, states and expert kernels see width C as before."""

    part: str
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    norm_eps: float = 1e-6

    def __post_init__(self):
        if self.part not in ("expand", "pre", "post", "sum"):
            raise ValueError(f"hyper-connection part {self.part!r}")

    @property
    def coef_width(self) -> int:
        return self.streams + self.streams * self.streams

    def infer(self, x: Shape, *rest):
        n, last = self.streams, x.dims[-1].size
        lead = tuple(_carry(d) for d in x.dims[:-1])
        if self.part == "expand":
            return (Shape(lead + (ParallelDim(last * n),), x.dtype,
                          x.replica),)
        if last % n:
            raise ValueError(f"a row of {last} values is not {n} streams")
        if self.part == "post":
            return (Shape(lead + (ParallelDim(last),), x.dtype, x.replica),)
        h = Shape(lead + (ParallelDim(last // n),), x.dtype, x.replica)
        if self.part == "sum":
            return (h,)
        return (h, Shape(lead + (ParallelDim(self.coef_width),),
                         DataType.FLOAT, x.replica))

    def weights(self, x: Shape, *rest):
        if self.part != "pre":
            return {}
        n, dt = self.streams, x.dtype
        width = 2 * n + n * n
        return {
            "phi": WeightSpec(TensorShape((x.dims[-1].size, width), dt)),
            "b": WeightSpec(TensorShape((width,), dt), "zeros"),
            "alpha": WeightSpec(TensorShape((3,), dt), "zeros"),
        }

    def flops(self, ins, outs):
        x = ins[0]
        tokens = math.prod(d.size for d in x.dims[:-1])
        n = self.streams
        if self.part == "pre":
            return tokens * x.dims[-1].size * (2 * (2 * n + n * n) + 2)
        return tokens * outs[0].dims[-1].size * 2 * n


@dataclasses.dataclass(frozen=True)
class CacheAttrs(OpAttrs):
    """Activation cache with user score (reference src/ops/cache.cc):
    carries a non-trainable buffer of the input; the trigger/alter flow is
    handled by RecompileState in the runtime."""

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        return {"cached": WeightSpec(x.to_shape(), "zeros", trainable=False)}


@dataclasses.dataclass(frozen=True)
class PipelineAttrs(OpAttrs):
    """Stacked transformer decoder blocks run as a GPipe pipeline.

    Fills the reference's OP_PIPELINE stub (ffconst.h / model.h:190-192 —
    enum + task IDs with no implementation) with a real TPU execution mode:
    the composite holds `layers` identical decoder blocks (RMSNorm -> GQA
    attention with RoPE -> RMSNorm -> SwiGLU MLP) with weights STACKED on a
    leading layer dim. On a mesh with a `pipe` axis the lowering runs them
    as layers/pipe_degree stages with microbatches circulating via
    lax.ppermute (parallel/pipeline.py); otherwise as a lax.scan over
    layers (layer-stacking — one compiled block instead of L copies).
    """

    layers: int
    heads: int
    kv_heads: int
    hidden: int
    n_microbatches: int = 4
    causal: bool = True
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5

    def infer(self, x: Shape):
        return (elementwise_like(x),)

    def weights(self, x: Shape):
        dim = x.dims[-1].size
        hd = dim // self.heads
        dt = x.dtype
        L = self.layers

        def w(*shape):
            return WeightSpec(TensorShape((L,) + shape, dt))

        return {
            "ln1": WeightSpec(TensorShape((L, dim), dt), "ones"),
            "wq": w(dim, self.heads, hd),
            "wk": w(dim, self.kv_heads, hd),
            "wv": w(dim, self.kv_heads, hd),
            "wo": w(self.heads, hd, dim),
            "ln2": WeightSpec(TensorShape((L, dim), dt), "ones"),
            "gate": w(dim, self.hidden),
            "up": w(dim, self.hidden),
            "down": w(self.hidden, dim),
        }

    def flops(self, ins, outs):
        x = ins[0]
        tokens = math.prod(d.size for d in x.dims[:-1])
        seq = x.dims[-2].size if x.ndim >= 2 else 1
        dim = x.dims[-1].size
        hd = dim // self.heads
        per_layer = (
            dim * self.heads * hd
            + 2 * dim * self.kv_heads * hd
            + self.heads * hd * dim
            + 3 * dim * self.hidden
        )
        dense = 2 * tokens * per_layer
        attn = 2 * tokens * seq * dim  # QK^T + PV at causal half density
        return self.layers * (dense + attn)
