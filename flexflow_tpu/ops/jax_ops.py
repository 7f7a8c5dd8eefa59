"""JAX lowerings for every operator.

Replaces the reference's CUDA/HIP kernel library (src/ops/kernels/*,
SURVEY.md §2.2) with XLA HLO: matmuls/convs hit the MXU via dot_general /
conv_general_dilated in the input dtype (bf16 when configured), elementwise
ops are fused by XLA, and the MoE dispatch uses dense one-hot matmuls
instead of scatter so it stays MXU-friendly. Pallas kernels for attention
live in flexflow_tpu.ops.pallas and are selected by the attention lowering
when profitable.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flexflow_tpu.ffconst import ActiMode, AggrMode, OpType, PoolType
from flexflow_tpu.ops.registry import LowerCtx, register_lowering


def apply_activation(x, act: ActiMode):
    if act == ActiMode.NONE:
        return x
    if act == ActiMode.RELU:
        return jax.nn.relu(x)
    if act == ActiMode.SIGMOID:
        return jax.nn.sigmoid(x)
    if act == ActiMode.TANH:
        return jnp.tanh(x)
    if act == ActiMode.GELU:
        return jax.nn.gelu(x)
    if act == ActiMode.SILU:
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {act}")


# ---------------------------------------------------------------------------
# sources


@register_lowering(OpType.INPUT)
def _input(attrs, inputs, params, ctx):
    raise RuntimeError("INPUT nodes are bound by the executor, not lowered")


@register_lowering(OpType.WEIGHT)
def _weight(attrs, inputs, params, ctx):
    return [params["weight"]]


@register_lowering(OpType.NOOP)
def _noop(attrs, inputs, params, ctx):
    return [inputs[0]]


# ---------------------------------------------------------------------------
# dense / conv / embedding / matmul


def contraction(forward, input_grad, kernel_grad):
    """`x @ w` of activations and a kernel of ONE dtype, the result at that
    dtype, as a function whose three dots each name the type they hand
    out: its own, and its transpose's two (the gradient of `x`, contracted
    over the kernel's columns, and of `w`, contracted over every leading
    dimension of `x`). None is the arrays' own dtype; a wider type is
    rounded to theirs after the dot.

    Why a dot's type is worth naming: where the contracted dimension is
    split over a mesh axis the partitioner places the all-reduce ON the
    dot, so the dot's type is what crosses the link: float32 partial sums
    added in float32 and rounded once after, or each chip's sum rounded
    first, half the bytes, and the sums added at the arrays' dtype. Within
    a chip nothing differs: the v5e accumulates a bfloat16 dot's
    contraction in float32 and rounds once, to the bit what the float32
    dot rounded after gives (`tools/chip_grad_precision.py`
    `rounded_once`)."""

    def dot(a, b, dims, like, out):
        return lax.dot_general(
            a, b, (dims, ((), ())),
            preferred_element_type=out or like.dtype).astype(like.dtype)

    def product(x, w):
        return dot(x, w, ((x.ndim - 1,), (0,)), x, forward)

    def run(x, w):
        return product(x, w), (x, w)

    def transpose(saved, g):
        x, w = saved
        rows = tuple(range(x.ndim - 1))
        return (dot(g, w, ((g.ndim - 1,), (1,)), x, input_grad),
                dot(x, g, (rows, rows), w, kernel_grad))

    contract = jax.custom_vjp(product)
    contract.defvjp(run, transpose)
    return contract


# A LINEAR's sums of ACTIVATIONS (forward where its rows are split, its input
# gradient where its columns are) cross a mesh axis in float32 and are
# rounded after; its KERNEL's gradient, contracted over the batch, is rounded
# a shard of the batch first and crosses at the activations' dtype. Decided
# leaf by leaf against a float32 reference on the chip, one group of
# reductions at a time (PERF.md section 6, PR 42): rounding the activations'
# sums first moved EVERY gradient leaf 1.5-4.9 % farther from float32 and the
# loss with them, the kernels' moved their own leaves by 0.4-0.8 % and no
# other, at half the gradient sync's bytes.
_linear_dot = contraction(jnp.float32, jnp.float32, None)


@register_lowering(OpType.LINEAR)
def _linear(attrs, inputs, params, ctx):
    (x,) = inputs
    y = _linear_dot(x, params["kernel"].astype(x.dtype))
    if attrs.use_bias:
        y = y + params["bias"].astype(x.dtype)
    return [apply_activation(y, attrs.activation)]


@register_lowering(OpType.CONV2D)
def _conv2d(attrs, inputs, params, ctx):
    (x,) = inputs
    y = lax.conv_general_dilated(
        x,
        params["kernel"].astype(x.dtype),
        window_strides=attrs.stride,
        padding=[(attrs.padding[0], attrs.padding[0]), (attrs.padding[1], attrs.padding[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=attrs.groups,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if attrs.use_bias:
        y = y + params["bias"].astype(x.dtype)[None, :, None, None]
    return [apply_activation(y, attrs.activation)]


@register_lowering(OpType.EMBEDDING)
def _embedding(attrs, inputs, params, ctx):
    (ids,) = inputs
    table = params["kernel"]
    out = jnp.take(table, ids, axis=0)
    if attrs.aggr == AggrMode.SUM:
        out = out.sum(axis=-2)
    elif attrs.aggr == AggrMode.AVG:
        out = out.mean(axis=-2)
    # masters are fp32; the op's declared dtype sets the activation dtype for
    # everything downstream (bf16 compute on the MXU)
    out = out.astype(attrs.dtype.jnp_dtype)
    return [out, table] if attrs.emit_table else [out]


@register_lowering(OpType.TIED_HEAD)
def _tied_head(attrs, inputs, params, ctx):
    """h E^T on the embedding's own leaf: no transposed copy of the table
    is made, the product contracts both operands' last dim."""
    h, table = inputs
    y = lax.dot_general(h, table.astype(h.dtype),
                        (((h.ndim - 1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if attrs.scale != 1.0:
        y = y * attrs.scale
    return [y.astype(h.dtype)]


@register_lowering(OpType.BATCH_MATMUL)
def _batch_matmul(attrs, inputs, params, ctx):
    a, b = inputs
    if ctx.seq_length is not None:
        # iteration-config truncation (reference a/b_seq_length_dim)
        if attrs.a_seq_length_dim >= 0:
            a = lax.slice_in_dim(a, 0, ctx.seq_length, axis=attrs.a_seq_length_dim)
        if attrs.b_seq_length_dim >= 0:
            b = lax.slice_in_dim(b, 0, ctx.seq_length, axis=attrs.b_seq_length_dim)
    y = jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    return [y]


# ---------------------------------------------------------------------------
# attention


def yarn_inv_freq(theta: float, head_dim: int, scaling) -> np.ndarray:
    """The rope's head_dim / 2 frequencies under YaRN, float32. `scaling`
    is MultiHeadAttentionAttrs.rope_scaling, (factor, original_max,
    beta_fast, beta_slow, attention_factor): pair i keeps theta^(-2i/d)
    where it turns more than beta_fast times over the original context,
    takes the same over `factor` where it turns fewer than beta_slow
    times, and a linear ramp of the two between the correction dims
    (d / 2) ln(original_max / (2 pi beta)) / ln theta, floored and
    ceiled and clipped to [0, d / 2 - 1]."""
    factor, original_max, beta_fast, beta_slow, _ = scaling
    d2 = head_dim // 2
    extrap = float(theta) ** (-np.arange(d2, dtype=np.float64) / d2)

    def dim_of(turns):
        return (d2 * math.log(original_max / (turns * 2 * math.pi))
                / math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d2 - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d2) - low) / (high - low), 0.0, 1.0)
    return (extrap / factor * ramp + extrap * (1.0 - ramp)).astype(
        np.float32)


def apply_rope(x, theta: float, pos_offset=0, scaling=None):
    """Rotary position embedding, half-split (rotate_half) convention.
    x: (B, S, H, D). `pos_offset` is a scalar, a (B,) vector of per-row
    offsets (continuous-batching decode: every slot sits at its own
    absolute position), or a (B, S) matrix of ABSOLUTE per-token
    positions (speculative tree verify: sibling draft nodes share a
    depth, so the flat node axis is not a position axis).

    Angles and sin/cos are computed in fp32 (position precision), but the
    rotation itself runs in the ACTIVATION dtype: upcasting the whole
    (B,S,H,D) tensor to fp32 made the backward materialize fp32 cotangent
    converts+relayouts (~1.3 GB/step at the 1b bench config,
    tools/hlo_transpose_audit.py); rotation values are in [-1,1] so bf16
    rotation costs ~2^-8 relative error — far below bf16 matmul noise.

    `scaling` (MultiHeadAttentionAttrs.rope_scaling) takes the
    frequencies from `yarn_inv_freq` and multiplies cos and sin by its
    attention_factor; None is the plain rope."""
    B, S, H, D = x.shape
    if D % 2 != 0:
        raise ValueError(f"RoPE requires an even head dim, got {D}")
    d2 = D // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, d2, dtype=jnp.float32) / d2)
    else:
        freqs = jnp.asarray(yarn_inv_freq(theta, D, scaling))
    off = jnp.asarray(pos_offset, jnp.float32)
    if off.ndim == 2:
        pos = off                                          # (B, S) absolute
    else:
        off = off.reshape(-1, 1)                           # (B|1, 1)
        pos = jnp.arange(S, dtype=jnp.float32)[None, :] + off  # (B|1, S)
    ang = pos[:, :, None] * freqs[None, None, :]  # (B|1, S, d2)
    if scaling is None:
        cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
        sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    else:
        cos = (jnp.cos(ang) * scaling[4])[:, :, None, :].astype(x.dtype)
        sin = (jnp.sin(ang) * scaling[4])[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def qkv_project(x, w, dt):
    """(B,S,E) x (E,H,D) -> (B,S,H,D) through the weight's 2D [E, H*D]
    view. Contracting the 3D weight directly lets XLA's forward and
    weight-grad dots prefer DIFFERENT minor-to-major layouts for it, and
    with donated buffers that materializes per-step relayout copies of the
    parameter AND its Adam state (~2.1 GB/step measured at the 1b bench
    config, tools/hlo_transpose_audit.py); the reshape is a bitcast of the
    canonical layout, so every use agrees and the copies vanish."""
    E, H, D = w.shape
    y = jnp.einsum("bse,ef->bsf", x, w.reshape(E, H * D).astype(dt))
    return y.reshape(*x.shape[:-1], H, D)


def attn_out_project(o, w, dt):
    """(B,S,H,D) x (H,D,E) -> (B,S,E) through the [H*D, E] view (same
    layout-pinning rationale as qkv_project)."""
    H, D, E = w.shape
    return jnp.einsum("bsf,fe->bse", o.reshape(*o.shape[:-2], H * D),
                      w.reshape(H * D, E).astype(dt))


def _dot_product_attention(q, k, v, causal: bool, scale: float,
                           dropout_rate: float = 0.0, dropout_rng=None,
                           mask=None):
    """q: (B,S,H,D), k/v: (B,T,Hkv,D) -> (B,S,H,D). fp32 softmax accumulate.
    `mask` (S, T) or per-row (B, S, T) overrides the causal triangle
    (KV-cache decode passes the absolute-position mask)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is None and causal:
        mask = jnp.tril(jnp.ones((S, T), dtype=bool))
    if mask is not None:
        m = mask[None, None] if mask.ndim == 2 else mask[:, None]
        logits = jnp.where(m, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _sharded_flash(q, k, v, mesh, causal, scale, interpret=False):
    """Run the Pallas flash kernel per shard under shard_map: batch stays
    sharded over `data`, heads over `model` (head-TP keeps the flash path —
    a bare pallas_call would force GSPMD to gather, VERDICT r1 weakness 3).
    The full sequence is local to every shard (seq-sharded attention goes
    through ring attention instead). GQA kv heads stay UNREPEATED when
    they divide the head axis (the kernel maps q heads onto kv heads);
    otherwise the repeat happens here so both specs shard evenly."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.ops.pallas import flash_attention
    from flexflow_tpu.parallel.compat import shard_map as _shard_map

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_ax = "data" if sizes.get("data", 1) > 1 and B % sizes["data"] == 0 else None
    h_ax = "model" if sizes.get("model", 1) > 1 and H % sizes["model"] == 0 else None
    from flexflow_tpu.parallel.comm_spec import flash_repeats_kv

    if flash_repeats_kv(H, Hkv, sizes.get("model", 1)):
        from flexflow_tpu.parallel.ring import repeat_kv

        k, v = repeat_kv(k, v, H // Hkv)
    spec = P(b_ax, None, h_ax, None)

    def fn(ql, kl, vl):
        return flash_attention(ql, kl, vl, causal=causal, scale=scale,
                               interpret=interpret)

    return _shard_map(fn, mesh, (spec, spec, spec), spec,
                      check_vma=False)(q, k, v)


def fused_attention(q, k, v, *, causal, scale, dropout=0.0, dropout_rng=None,
                    mesh=None):
    """Dispatch: Pallas flash kernel on TPU when shapes/config allow —
    wrapped in shard_map on multi-device meshes so DP/head-TP strategies
    keep the flash path — XLA dot-product attention otherwise. GQA kv
    heads reach the flash kernels unrepeated (the kernel index maps fold
    the repeat); the XLA fallback repeats internally. Sets
    LAST_ATTENTION_KERNEL for observability."""
    import os

    global LAST_ATTENTION_KERNEL

    from flexflow_tpu.ops.pallas import (
        flash_attention,
        flash_attention_available,
    )

    force_interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    single = mesh is None or getattr(mesh, "size", 1) == 1
    avail = flash_attention_available(q.shape[1], k.shape[1], dropout=dropout,
                                      interpret=force_interp)
    if avail and single:
        LAST_ATTENTION_KERNEL = "pallas_flash"
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=force_interp)
    if avail and not single:
        LAST_ATTENTION_KERNEL = "pallas_flash_shard_map"
        return _sharded_flash(q, k, v, mesh, causal, scale,
                              interpret=force_interp)
    LAST_ATTENTION_KERNEL = "xla_dot_product"
    if jax.default_backend() == "tpu":
        # on a TPU the S x T score matrix in HBM is a degraded mode
        # somebody has to see, not a quiet alternative
        import logging

        logging.getLogger(__name__).warning(
            "attention: Pallas flash kernel unavailable for q %s / k %s "
            "(dropout=%s, FF_TPU_NO_FLASH=%s); using XLA dot-product "
            "attention", q.shape, k.shape, dropout,
            os.environ.get("FF_TPU_NO_FLASH"))
    return _dot_product_attention(q, k, v, causal, scale,
                                  dropout_rate=dropout, dropout_rng=dropout_rng)


LAST_ATTENTION_KERNEL = "none"


def cached_attention(q, k, v, cache_k, cache_v, pos, *, scale,
                     rope_theta=None, rope_scaling=None, window=None):
    """Autoregressive decode/prefill step shared by MHA, ring attention,
    and the PIPELINE composite: rope at absolute positions (when
    `rope_theta`), append k/v into the cache at `pos`, attend over
    everything written so far with a causal absolute-position mask
    (slots past the write head stay masked). `pos` is a scalar for
    lockstep generate() or a (B,) vector for continuous batching (each
    slot decodes at its own depth; a freshly admitted slot's stale cache
    rows sit at kpos > qpos until overwritten). A sliding `window` also
    masks the rows at or beyond `window` before the query.

    Returns (attention output, new k cache, new v cache)."""
    dt = q.dtype
    pos_v = jnp.asarray(pos)
    if rope_theta is not None:
        q = apply_rope(q, rope_theta, pos_offset=pos, scaling=rope_scaling)
        k = apply_rope(k, rope_theta, pos_offset=pos, scaling=rope_scaling)
    if pos_v.ndim == 0:
        kc = lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0)
        )
        vc = lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0)
        )
        qpos = pos + jnp.arange(q.shape[1])      # absolute q positions
        kpos = jnp.arange(kc.shape[1])           # cache slots
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
    else:
        def write_row(cache_row, new_row, p):
            return lax.dynamic_update_slice(cache_row, new_row, (p, 0, 0))

        kc = jax.vmap(write_row)(cache_k, k.astype(cache_k.dtype), pos_v)
        vc = jax.vmap(write_row)(cache_v, v.astype(cache_v.dtype), pos_v)
        qpos = pos_v[:, None] + jnp.arange(q.shape[1])[None, :]  # (B,S)
        kpos = jnp.arange(kc.shape[1])
        mask = kpos[None, None, :] <= qpos[:, :, None]           # (B,S,T)
        if window is not None:
            mask &= qpos[:, :, None] - kpos[None, None, :] < window
    out = _dot_product_attention(
        q, kc.astype(dt), vc.astype(dt), causal=False,
        scale=scale, mask=mask,
    )
    return out, kc, vc


def _mha_qkv(attrs, inputs, params):
    """The three projections with their bias: (q, k, v, the rows' dtype)."""
    q_in = inputs[0]
    k_in = inputs[1] if len(inputs) > 1 else q_in
    v_in = inputs[2] if len(inputs) > 2 else k_in
    dt = q_in.dtype
    q = qkv_project(q_in, params["wq"], dt)
    k = qkv_project(k_in, params["wk"], dt)
    v = qkv_project(v_in, params["wv"], dt)
    if attrs.use_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    return q, k, v, dt


def _mha_out(attrs, out, params, dt):
    y = attn_out_project(out, params["wo"], dt)
    if attrs.use_bias:
        y = y + params["bo"].astype(dt)
    return y


def _window_kw(attrs):
    """What a window or a scaled rope adds to a cache path's call: a full
    layer with a plain rope calls it as it always has, so its programs do
    not change with what other layers can do."""
    if attrs.window is None and attrs.rope_scaling is None:
        return {}
    return {"window": attrs.window, "rope_scaling": attrs.rope_scaling}


def _mha_paged(attrs, inputs, params, ctx):
    """Every paged step (decode, a chunked-prefill chunk, a tree verify)
    is the SAME ragged call: the cache is a global page pool, this slot's
    rows are reached through its page table, and the (q_lens, depths,
    anc) descriptor says which of the S window rows are live and what
    they may see (flexflow_tpu.paged.attention: one Pallas kernel or the
    gather fallback behind one gate). The node names its four parts:
    `QKV` and `OUT` here, the rope, `KV_WRITE` and `ATTEND` in the call."""
    from flexflow_tpu.paged import attention as pa

    with jax.named_scope(pa.QKV):
        q, k, v, dt = _mha_qkv(attrs, inputs, params)
    kw = _window_kw(attrs)
    if "k_scale" in ctx.kv_cache:
        # quantized pool: the scale sidecar rides the same per-node
        # caches dict (paged/quant.py), so append quantizes under
        # grow-only scales and both attention paths dequantize on load
        kw.update(k_scales=ctx.kv_cache["k_scale"],
                  v_scales=ctx.kv_cache["v_scale"])
    out, *pools = pa.ragged_paged_attention(
        q, k, v, ctx.kv_cache["k"], ctx.kv_cache["v"], ctx.page_tables,
        ctx.cache_position, ctx.ragged_q_lens, ctx.ragged_depths,
        ctx.ragged_anc, scale=attrs.scale,
        rope_theta=attrs.rope_theta if attrs.rope else None, **kw)
    ctx.cache_updates.update(zip(("k", "v", "k_scale", "v_scale"), pools))
    with jax.named_scope(pa.OUT):
        return [_mha_out(attrs, out, params, dt)]


@register_lowering(OpType.MULTIHEAD_ATTENTION)
def _mha(attrs, inputs, params, ctx):
    if ctx.kv_cache is not None and ctx.page_tables is not None:
        return _mha_paged(attrs, inputs, params, ctx)
    q, k, v, dt = _mha_qkv(attrs, inputs, params)
    if ctx.kv_cache is not None:
        out, kc, vc = cached_attention(
            q, k, v, ctx.kv_cache["k"], ctx.kv_cache["v"],
            ctx.cache_position, scale=attrs.scale,
            rope_theta=attrs.rope_theta if attrs.rope else None,
            **_window_kw(attrs),
        )
        ctx.cache_updates["k"] = kc
        ctx.cache_updates["v"] = vc
    else:
        if attrs.rope:
            q = apply_rope(q, attrs.rope_theta, scaling=attrs.rope_scaling)
            k = apply_rope(k, attrs.rope_theta, scaling=attrs.rope_scaling)
        drop_rng = ctx.rng if (ctx.training and attrs.dropout > 0.0) else None
        if attrs.window is not None:
            # the flash kernels have no window: the masked einsum (the
            # S x S scores in HBM; training this layer type at length is
            # not what it is for)
            i = jnp.arange(q.shape[1])
            seen = (i[None, :] <= i[:, None]) & (
                i[:, None] - i[None, :] < attrs.window)
            out = _dot_product_attention(
                q, k, v, False, attrs.scale,
                dropout_rate=attrs.dropout if ctx.training else 0.0,
                dropout_rng=drop_rng, mask=seen)
        else:
            out = fused_attention(
                q, k, v, causal=attrs.causal, scale=attrs.scale,
                dropout=attrs.dropout if ctx.training else 0.0,
                dropout_rng=drop_rng, mesh=ctx.mesh,
            )
    return [_mha_out(attrs, out, params, dt)]


@register_lowering(OpType.LATENT_ATTENTION)
def _latent_attention(attrs, inputs, params, ctx):
    """Multi-head latent attention (ops/latent_attention.py). Without a
    cache the naive form over the whole sequence; with a page pool the
    absorbed form over one `[c_kv | k_r]` row a token (entry "c" of the
    node's cache). There is no dense decode cache for it: the latent row
    is what makes the layer worth caching, and only the pool holds it."""
    from flexflow_tpu.ops import latent_attention as la

    (x,) = inputs
    if ctx.kv_cache is None:
        return [la.naive_attention(attrs, x, params)]
    if ctx.page_tables is None:
        raise NotImplementedError(
            "latent attention decodes through the page pool only: serve "
            "with serve_generation(paged=True)")
    y, pools, stats = la.paged_attention(attrs, x, params, ctx)
    ctx.cache_updates.update(pools)
    if stats is not None:
        ctx.state_updates["dsa_stats"] = stats
    return [y]


@register_lowering(OpType.KDA_ATTENTION)
def _kda_attention(attrs, inputs, params, ctx):
    """A delta-rule linear-attention layer (ops/kda_attention.py). Without
    a cache the whole sequence from a zero state; in a paged launch the
    node's cache entry is its STATE a slot ("s", "conv"), continued from
    the rows the launch's items name. There is no dense decode cache for
    it: the state lives beside pages only."""
    from flexflow_tpu.ops import kda_attention as kda

    (x,) = inputs
    if ctx.kv_cache is None:
        return [kda.dense_attention(attrs, x, params)]
    if ctx.page_tables is None:
        raise NotImplementedError(
            "a KDA layer decodes from its per-slot state only: serve "
            "with serve_generation(paged=True)")
    y, state = kda.paged_attention(attrs, x, params, ctx)
    ctx.cache_updates.update(state)
    return [y]


@register_lowering(OpType.MAMBA2)
def _mamba2(attrs, inputs, params, ctx):
    """A Mamba-2 state-space mixer (ops/mamba2.py): the whole sequence
    from a zero state without a cache, the slots' states continued in a
    paged launch; like a KDA layer it has no dense decode cache."""
    from flexflow_tpu.ops import mamba2

    (x,) = inputs
    if ctx.kv_cache is None:
        return [mamba2.dense_mixer(attrs, x, params)]
    if ctx.page_tables is None:
        raise NotImplementedError(
            "a Mamba-2 layer decodes from its per-slot state only: serve "
            "with serve_generation(paged=True)")
    y, state = mamba2.paged_mixer(attrs, x, params, ctx)
    ctx.cache_updates.update(state)
    return [y]


@register_lowering(OpType.RING_ATTENTION)
def _ring_attention(attrs, inputs, params, ctx):
    # Sequence-parallel lowering lives in flexflow_tpu.parallel.ring; when the
    # seq dim is unsharded this is plain attention.
    if ctx.kv_cache is not None:
        # autoregressive decode is sequential — there is no sequence to
        # shard — and ring attention's weights/math are identical to
        # MULTIHEAD_ATTENTION's, so the cached path is shared verbatim
        # (VERDICT r2 weakness 3: SP graphs previously could not decode)
        return _mha(attrs, inputs, params, ctx)
    from flexflow_tpu.parallel.ring import ring_attention_lowering

    return ring_attention_lowering(attrs, inputs, params, ctx)


# ---------------------------------------------------------------------------
# elementwise


_BINARY = {
    "add": jnp.add,
    "subtract": jnp.subtract,
    "multiply": jnp.multiply,
    "divide": jnp.divide,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


@register_lowering(OpType.ELEMENT_BINARY)
def _element_binary(attrs, inputs, params, ctx):
    a, b = inputs
    # learned-position tables (attrs.position_table, set by
    # add_position_embedding) under KV-cache decode: the (S, E) row table
    # adds its rows at the CURRENT cache position — prefill sees rows
    # [pos, pos+s), a single-token step its own row. An explicit graph
    # property rather than a shape heuristic: a chunked prefill starting
    # at pos>0 with chunk length == table size would fool any sniffing.
    # generate() guards total length against the table size up front
    # (dynamic_slice clamps rather than faults inside jit).
    if getattr(attrs, "position_table", False) and ctx.cache_position is not None:
        pos = jnp.asarray(ctx.cache_position)
        if pos.ndim == 0:
            rows = lax.dynamic_slice_in_dim(b, pos, a.shape[1], axis=0)
            b = rows[None]
        elif ctx.ragged_depths is not None:
            # ragged paged step: row i sits at absolute position
            # pos + depth[i] — arange for chunks/decode, node depth for
            # tree verify (sibling branches share a row of the table)
            b = b[pos[:, None] + ctx.ragged_depths]
        else:
            # continuous batching: per-row positions. S=1 is the dense
            # server's decode step; S>1 rows sit at pos..pos+S (the paged
            # step always passes ragged_depths and takes the branch
            # above — the gather clamps rows past the table)
            rows = pos[:, None] + jnp.arange(a.shape[1])[None, :]
            b = b[rows]
    return [_BINARY[attrs.kind](a, b)]


@register_lowering(OpType.ELEMENT_UNARY)
def _element_unary(attrs, inputs, params, ctx):
    (x,) = inputs
    k, s = attrs.kind, attrs.scalar
    fns = {
        "exp": jnp.exp,
        "sin": jnp.sin,
        "cos": jnp.cos,
        "relu": jax.nn.relu,
        "gelu": jax.nn.gelu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "elu": jax.nn.elu,
        "rsqrt": lax.rsqrt,
        "silu": jax.nn.silu,
        "identity": lambda v: v,
        "pow": lambda v: jnp.power(v, s),
        "scalar_add": lambda v: v + s,
        "scalar_sub": lambda v: v - s,
        "scalar_multiply": lambda v: v * s,
        "scalar_truediv": lambda v: v / s,
        "scalar_min": lambda v: jnp.minimum(v, jnp.asarray(s, v.dtype)),
        "clip": lambda v: jnp.clip(v, jnp.asarray(-s, v.dtype),
                                   jnp.asarray(s, v.dtype)),
    }
    return [fns[k](x)]


# ---------------------------------------------------------------------------
# shape ops


@register_lowering(OpType.RESHAPE)
def _reshape(attrs, inputs, params, ctx):
    return [inputs[0].reshape(attrs.shape)]


@register_lowering(OpType.FLAT)
def _flat(attrs, inputs, params, ctx):
    x = inputs[0]
    return [x.reshape(x.shape[0], -1)]


@register_lowering(OpType.TRANSPOSE)
def _transpose(attrs, inputs, params, ctx):
    return [jnp.transpose(inputs[0], attrs.perm)]


@register_lowering(OpType.REVERSE)
def _reverse(attrs, inputs, params, ctx):
    return [jnp.flip(inputs[0], axis=attrs.axis)]


@register_lowering(OpType.CONCAT)
def _concat(attrs, inputs, params, ctx):
    return [jnp.concatenate(inputs, axis=attrs.axis)]


@register_lowering(OpType.SPLIT)
def _split(attrs, inputs, params, ctx):
    x = inputs[0]
    outs = []
    off = 0
    for sz in attrs.sizes:
        outs.append(lax.slice_in_dim(x, off, off + sz, axis=attrs.axis))
        off += sz
    return outs


@register_lowering(OpType.CAST)
def _cast(attrs, inputs, params, ctx):
    return [inputs[0].astype(attrs.dtype.jnp_dtype)]


# ---------------------------------------------------------------------------
# norm / pool / softmax / dropout


@register_lowering(OpType.POOL2D)
def _pool2d(attrs, inputs, params, ctx):
    (x,) = inputs
    kh, kw = attrs.kernel
    sh, sw = attrs.stride
    ph, pw = attrs.padding
    window = (1, 1, kh, kw)
    strides = (1, 1, sh, sw)
    pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
    if attrs.pool_type == PoolType.MAX:
        y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        y = y.astype(x.dtype)
    else:
        s = lax.reduce_window(
            x.astype(jnp.float32), 0.0, lax.add, window, strides, pads
        )
        y = (s / (kh * kw)).astype(x.dtype)
    return [apply_activation(y, attrs.activation)]


@register_lowering(OpType.BATCH_NORM)
def _batch_norm(attrs, inputs, params, ctx):
    (x,) = inputs
    scale = params["scale"][None, :, None, None]
    bias = params["bias"][None, :, None, None]
    if ctx.training:
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=(0, 2, 3))
        var = xf.var(axis=(0, 2, 3))
        m = attrs.momentum
        ctx.state_updates["running_mean"] = (
            (1 - m) * params["running_mean"] + m * mean
        ).astype(params["running_mean"].dtype)
        ctx.state_updates["running_var"] = (
            (1 - m) * params["running_var"] + m * var
        ).astype(params["running_var"].dtype)
    else:
        mean, var = params["running_mean"], params["running_var"]
    inv = lax.rsqrt(var + attrs.eps)[None, :, None, None]
    y = (x - mean[None, :, None, None]) * inv * scale + bias
    y = y.astype(x.dtype)
    return [jax.nn.relu(y) if attrs.relu else y]


@register_lowering(OpType.LAYER_NORM)
def _layer_norm(attrs, inputs, params, ctx):
    (x,) = inputs
    axes = tuple(a % x.ndim for a in attrs.axes)
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=axes, keepdims=True)
    var = xf.var(axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + attrs.eps)
    if attrs.elementwise_affine:
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return [y.astype(x.dtype)]


@register_lowering(OpType.RMS_NORM)
def _rms_norm(attrs, inputs, params, ctx):
    (x,) = inputs
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(ms + attrs.eps) * params["scale"].astype(jnp.float32)
    return [y.astype(x.dtype)]


@register_lowering(OpType.SOFTMAX)
def _softmax(attrs, inputs, params, ctx):
    return [jax.nn.softmax(inputs[0], axis=attrs.axis)]


@register_lowering(OpType.DROPOUT)
def _dropout(attrs, inputs, params, ctx):
    (x,) = inputs
    if not ctx.training or attrs.rate == 0.0:
        return [x]
    keep = 1.0 - attrs.rate
    mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
    return [jnp.where(mask, x / keep, 0).astype(x.dtype)]


# ---------------------------------------------------------------------------
# gather / reduce / topk


@register_lowering(OpType.GATHER)
def _gather(attrs, inputs, params, ctx):
    x, idx = inputs
    return [jnp.take_along_axis(x, idx, axis=attrs.axis)]


@register_lowering(OpType.REDUCE_SUM)
def _reduce(attrs, inputs, params, ctx):
    (x,) = inputs
    fn = jnp.sum if attrs.kind == "sum" else jnp.mean
    return [fn(x, axis=attrs.axes, keepdims=attrs.keepdims)]


@register_lowering(OpType.MEAN)
def _mean(attrs, inputs, params, ctx):
    (x,) = inputs
    return [jnp.mean(x, axis=attrs.axes, keepdims=attrs.keepdims)]


@register_lowering(OpType.TOPK)
def _topk(attrs, inputs, params, ctx):
    (x,) = inputs
    vals, idx = lax.top_k(x, attrs.k)
    return [vals, idx.astype(jnp.int32)]


# ---------------------------------------------------------------------------
# recurrent


@register_lowering(OpType.LSTM)
def _lstm(attrs, inputs, params, ctx):
    """LSTM over the whole sequence (reference nmt/lstm.cu, one cuDNN node
    per timestep-block). TPU shape: the input projection x@wx for ALL
    timesteps is one big MXU matmul outside the recurrence; lax.scan carries
    only the (batch, 4*hidden) recurrent matmul. Cell state accumulates in
    fp32; gate order i,f,g,o matches torch.nn.LSTM."""
    x = inputs[0]  # (B, S, D)
    B, S, _ = x.shape
    H = attrs.hidden
    wx = params["wx"].astype(x.dtype)
    wh = params["wh"].astype(x.dtype)
    h0 = inputs[1] if len(inputs) > 1 else jnp.zeros((B, H), x.dtype)
    c0 = (inputs[2] if len(inputs) > 2 else jnp.zeros((B, H), x.dtype))
    if attrs.reverse:
        x = jnp.flip(x, axis=1)
    xg = jnp.dot(x, wx, preferred_element_type=jnp.float32).astype(x.dtype)
    if attrs.use_bias:
        xg = xg + params["bias"].astype(x.dtype)

    def step(carry, xt):
        h, c = carry  # (B,H) activation dtype, (B,H) fp32
        gates = (
            xt + jnp.dot(h, wh, preferred_element_type=jnp.float32).astype(x.dtype)
        ).astype(jnp.float32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = (jax.nn.sigmoid(o) * jnp.tanh(c)).astype(x.dtype)
        return (h, c), h

    (h_n, c_n), ys = lax.scan(
        step, (h0, c0.astype(jnp.float32)), xg.transpose(1, 0, 2)
    )
    y = ys.transpose(1, 0, 2)
    if attrs.reverse:
        y = jnp.flip(y, axis=1)
    return [y, h_n, c_n.astype(x.dtype)]


# ---------------------------------------------------------------------------
# MoE: group_by / aggregate / fused experts
#
# TPU-native design: dense capacity-based dispatch. Scatter/gather per token
# (the reference's group_by/aggregate CUDA kernels) is replaced by one-hot
# dispatch/combine matmuls which run on the MXU and shard cleanly over an
# expert mesh axis.


def _dispatch_mask(assign, n_experts: int, capacity: int):
    """assign: (batch, k) int expert ids -> dispatch (batch, k, n_experts,
    capacity) one-hot, with tokens beyond capacity dropped (priority = batch
    order, matching the reference's sequential scan in group_by.cu)."""
    onehot = jax.nn.one_hot(assign, n_experts, dtype=jnp.float32)  # (b,k,n)
    # position of each (token, slot) within its expert queue, flattened in
    # (k-major, batch) order like the reference's linear scan
    b, k = assign.shape
    flat = onehot.transpose(1, 0, 2).reshape(b * k, n_experts)  # k-major
    pos = jnp.cumsum(flat, axis=0) - flat  # (b*k, n)
    pos = pos.reshape(k, b, n_experts).transpose(1, 0, 2)  # (b,k,n)
    keep = pos < capacity
    onehot = onehot * keep
    cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    return onehot[..., None] * cap_onehot  # (b,k,n,cap)


@register_lowering(OpType.GROUP_BY)
def _group_by(attrs, inputs, params, ctx):
    x, assign = inputs  # (b, d), (b, k)
    b = x.shape[0]
    k = assign.shape[-1]
    cap = attrs.capacity(b, k)
    disp = _dispatch_mask(assign, attrs.n_experts, cap)  # (b,k,n,cap)
    disp = disp.sum(axis=1)  # (b,n,cap) — a token goes to each assigned expert
    outs = jnp.einsum("bnc,bd->ncd", disp.astype(x.dtype), x)
    return [outs[i] for i in range(attrs.n_experts)]


@register_lowering(OpType.AGGREGATE)
def _aggregate(attrs, inputs, params, ctx):
    # inputs: gate_preds (b,k), gate_assign (b,k), true_gate_assign (b,k),
    # full_gate probs (b,n), expert outputs n×(cap, d)
    gate_preds, gate_assign = inputs[0], inputs[1]
    experts = jnp.stack(inputs[4:], axis=0)  # (n, cap, d)
    b, k = gate_preds.shape
    cap = experts.shape[1]
    disp = _dispatch_mask(gate_assign.astype(jnp.int32), attrs.n_experts, cap)
    # combine weights: gate prob on kept (token, expert, slot) triples
    combine = (disp * gate_preds[..., None, None].astype(jnp.float32)).sum(axis=1)
    y = jnp.einsum("bnc,ncd->bd", combine.astype(experts.dtype), experts)
    if attrs.lambda_bal > 0.0 and ctx.training:
        # load-balance gradient through the full gate distribution — the
        # reference computes this in aggregate's backward (aggregate.cu,
        # lambda_bal); functionally it is the Switch-style aux loss
        # n·Σ_e f_e·p̄_e, differentiable through inputs[3]
        full_gate = inputs[3].astype(jnp.float32)  # (b, n)
        counts = disp.sum(axis=(0, 1, 3))  # tokens kept per expert
        frac = counts / jnp.maximum(counts.sum(), 1.0)
        mean_prob = full_gate.mean(axis=0)
        ctx.state_updates["__aux_loss__"] = (
            attrs.n_experts * jnp.sum(frac * mean_prob) * attrs.lambda_bal
        )
    return [y]


@register_lowering(OpType.AGGREGATE_SPEC)
def _aggregate_spec(attrs, inputs, params, ctx):
    gate_preds, gate_assign = inputs[0], inputs[1]
    experts = jnp.stack(inputs[4:], axis=0)
    b, k = gate_preds.shape
    cap = experts.shape[1]
    disp = _dispatch_mask(gate_assign.astype(jnp.int32), attrs.n_experts, cap)
    # (b,k,n,cap) -> per-slot outputs stacked to (b*k, d)
    per_slot = jnp.einsum("bknc,ncd->bkd", disp.astype(experts.dtype), experts)
    return [per_slot.reshape(b * k, -1)]


def _sorted_dispatch(topi, t: int, n_experts: int, cap: int):
    """Token-sort dispatch plan. `topi` (t, k) int expert ids.

    Slots are prioritized in the same k-major arrival order as
    _dispatch_mask's cumsum (slot f = k_idx * t + token), so the two
    implementations drop exactly the same tokens at capacity. Returns
      slot_of_flat: (t*k,) buffer row per flat slot (n*cap = dropped)
      kept_per_expert: (n,) tokens kept per expert after capacity
    All O(t*k log(t*k)) sort work — no (t, n, cap) materialization.
    Reference analog: the sequential expert-queue scan in group_by.cu,
    re-expressed as sort + rank for a data-parallel machine."""
    k = topi.shape[1]
    flat_e = topi.astype(jnp.int32).transpose(1, 0).reshape(-1)  # k-major
    order = jnp.argsort(flat_e, stable=True)  # arrival order within expert
    sorted_e = flat_e[order]
    # rank within its expert = global sorted position - expert start
    start_of_own = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - start_of_own.astype(jnp.int32)
    valid = pos_in_e < cap
    buf_slot = jnp.where(valid, sorted_e * cap + pos_in_e, n_experts * cap)
    # invert the sort: flat slot f -> its buffer row
    slot_of_flat = jnp.zeros((t * k,), jnp.int32).at[order].set(buf_slot)
    counts = jnp.searchsorted(
        sorted_e, jnp.arange(n_experts, dtype=jnp.int32), side="right"
    ) - jnp.searchsorted(
        sorted_e, jnp.arange(n_experts, dtype=jnp.int32), side="left"
    )
    kept = jnp.minimum(counts, cap)
    return slot_of_flat, kept


@register_lowering(OpType.EXPERT_SHARE)
def _expert_share(attrs, inputs, params, ctx):
    """One chip's share of a dropless SwiGLU expert layer plus its
    shared expert (ops/expert_share.py). A paged serving step also
    leaves the launch's counters (assignments to held experts, held
    experts hit, held, padded rows) in ctx.state_updates["moe_stats"]
    for the scheduler; no other mode reports them. A ragged launch's pad
    rows (entry b's rows from q_lens[b] on: an idle or mid-prefill slot
    of a decode tick carries token 0 in every one) go to no expert: left
    in, they all chose the same experts, 0 to 4 of them held by the draw
    of the weights, and streamed those every tick."""
    from flexflow_tpu.ops.expert_share import expert_share

    live = None
    if ctx.page_tables is not None and ctx.ragged_q_lens is not None:
        live = (jnp.arange(inputs[0].shape[1], dtype=jnp.int32)[None, :]
                < ctx.ragged_q_lens[:, None])
    y, stats = expert_share(attrs, inputs[0], params, live)
    if ctx.page_tables is not None:
        ctx.state_updates["moe_stats"] = stats
    return [y]


@register_lowering(OpType.HYPER_CONNECTION)
def _hyper_connection(attrs, inputs, params, ctx):
    """One part of the mixing of a multi-stream residual around a block
    (ops/hyper_connection.py); row-wise, so the same in every mode."""
    from flexflow_tpu.ops import hyper_connection as hc

    if attrs.part == "expand":
        return [hc.expand(attrs, inputs[0])]
    if attrs.part == "pre":
        return list(hc.pre(attrs, inputs[0], params))
    if attrs.part == "post":
        return [hc.post(attrs, *inputs)]
    return [hc.collapse(attrs, inputs[0])]


@register_lowering(OpType.EXPERTS)
def _experts(attrs, inputs, params, ctx):
    """Fused MoE FFN: top-k gate -> capacity dispatch -> two-layer expert
    FFN (einsum over stacked expert weights) -> weighted combine. Auxiliary
    load-balance loss (Switch-style) is written into ctx.state_updates for
    the executor to add to the loss.

    attrs.dispatch picks the dispatch implementation:
      "sort"  (default) — argsort tokens by expert, scatter rows into a
        static (n*cap, d) buffer, gather back after the expert matmuls.
        O(tokens*dim) data movement like the reference's scatter kernels
        (group_by.cu / aggregate.cu); scales to Mixtral shapes where the
        one-hot mask alone would be GiBs.
      "dense" — one-hot dispatch/combine einsums; numerics oracle.
    """
    x, gate_logits = inputs  # (..., d), (..., n)
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    gl = gate_logits.reshape(-1, attrs.n_experts)
    t = xt.shape[0]
    probs = jax.nn.softmax(gl.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(probs, attrs.k)  # (t,k)
    if attrs.normalize:
        topv = topv / topv.sum(axis=-1, keepdims=True)
    cap = attrs.capacity(t)
    n = attrs.n_experts

    if getattr(attrs, "dispatch", "sort") == "sort":
        slot_of_flat, kept = _sorted_dispatch(topi, t, n, cap)
        token_of_flat = jnp.tile(jnp.arange(t, dtype=jnp.int32), attrs.k)
        # scatter token rows into the expert buffer; row n*cap collects
        # dropped slots and is sliced off. (token, expert) pairs are
        # unique (top_k), so kept rows get exactly one write.
        buf = jnp.zeros((n * cap + 1, d), xt.dtype).at[slot_of_flat].set(
            xt[token_of_flat], mode="drop", unique_indices=False
        )
        buf = buf[:-1].reshape(n, cap, d)
        # expert-parallel: pin the buffer to the weights' expert axis so
        # the scatter lowers to the token all-to-all over that axis and
        # each device runs only its expert slice of the matmuls (the
        # reference's Repartition/Combine EP over NCCL, done by GSPMD)
        view = ctx.sharding
        if (ctx.mesh is not None and view is not None
                and "w1" in getattr(view, "weight_specs", {})):
            from jax.sharding import NamedSharding

            from flexflow_tpu.parallel.sharding import (
                prune_spec,
                spec_to_partition_spec,
            )

            spec = prune_spec(
                view.weight_specs["w1"][:1] + ((), ()),
                buf.shape, ctx.mesh,
            )
            buf = lax.with_sharding_constraint(
                buf, NamedSharding(ctx.mesh, spec_to_partition_spec(spec))
            )
        h = jnp.einsum("ncd,ndh->nch", buf, params["w1"].astype(xt.dtype))
        h = apply_activation(h, attrs.activation)
        o = jnp.einsum("nch,nho->nco", h, params["w2"].astype(xt.dtype))
        o_flat = jnp.concatenate(
            [o.reshape(n * cap, attrs.out_dim),
             jnp.zeros((1, attrs.out_dim), o.dtype)], axis=0
        )
        per_slot = o_flat[slot_of_flat]  # (t*k, out) — dropped slots -> 0
        w = topv.transpose(1, 0).reshape(-1, 1).astype(per_slot.dtype)
        y = (per_slot * w).reshape(attrs.k, t, attrs.out_dim).sum(axis=0)
        kept_f = kept.astype(jnp.float32)
        frac = kept_f / jnp.maximum(kept_f.sum(), 1.0)
    else:
        disp = _dispatch_mask(topi.astype(jnp.int32), n, cap)  # (t,k,n,c)
        combine = disp * topv[..., None, None]
        disp_tok = disp.sum(axis=1)  # (t,n,c)
        buf = jnp.einsum("tnc,td->ncd", disp_tok.astype(xt.dtype), xt)
        h = jnp.einsum("ncd,ndh->nch", buf, params["w1"].astype(xt.dtype))
        h = apply_activation(h, attrs.activation)
        o = jnp.einsum("nch,nho->nco", h, params["w2"].astype(xt.dtype))
        y = jnp.einsum("tknc,nco->to", combine.astype(o.dtype), o)
        frac = disp_tok.sum(axis=(0, 2)) / jnp.maximum(disp_tok.sum(), 1.0)
    # Switch-transformer load-balance aux loss: n * sum_e f_e * p_e
    mean_prob = probs.mean(axis=0)
    aux = attrs.n_experts * jnp.sum(frac * mean_prob) * attrs.lambda_bal
    ctx.state_updates["__aux_loss__"] = aux
    return [y.reshape(*orig_shape[:-1], attrs.out_dim)]


@register_lowering(OpType.CACHE)
def _cache(attrs, inputs, params, ctx):
    (x,) = inputs
    if ctx.training:
        ctx.state_updates["cached"] = x
        return [x]
    return [params["cached"]]


# ---------------------------------------------------------------------------
# pipeline composite (fills the reference's OP_PIPELINE stub — see
# ops/attrs.py PipelineAttrs and parallel/pipeline.py)


def _decoder_block(p, h, attrs, mesh=None, cache=None):
    """One llama decoder block on per-layer params `p` (matches the
    unstacked builder: rms_norm -> GQA+RoPE attention -> rms_norm ->
    SwiGLU, residuals around both halves). `mesh` must be None inside the
    GPipe shard_map worker (already device-local) and ctx.mesh on the
    fallback scan path (the flash dispatcher needs it to pick the
    shard_map-wrapped kernel on multi-device meshes).

    `cache` = (cache_k, cache_v, pos) switches the attention into the
    shared autoregressive cached path; the return becomes
    (h, new_k_cache, new_v_cache)."""
    dt = h.dtype

    def rms(x, scale):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * lax.rsqrt(ms + attrs.norm_eps)
                * scale.astype(jnp.float32)).astype(dt)

    hd = h.shape[-1] // attrs.heads
    a = rms(h, p["ln1"])
    q = qkv_project(a, p["wq"], dt)
    k = qkv_project(a, p["wk"], dt)
    v = qkv_project(a, p["wv"], dt)
    kc = vc = None
    if cache is not None:
        cache_k, cache_v, pos = cache
        o, kc, vc = cached_attention(
            q, k, v, cache_k, cache_v, pos, scale=1.0 / (hd**0.5),
            rope_theta=attrs.rope_theta,
        )
    else:
        q = apply_rope(q, attrs.rope_theta)
        k = apply_rope(k, attrs.rope_theta)
        o = fused_attention(q, k, v, causal=attrs.causal,
                            scale=1.0 / (hd**0.5), mesh=mesh)
    h = h + attn_out_project(o, p["wo"], dt)
    m = rms(h, p["ln2"])
    g = jnp.einsum("bse,eh->bsh", m, p["gate"].astype(dt))
    u = jnp.einsum("bse,eh->bsh", m, p["up"].astype(dt))
    h = h + jnp.einsum("bsh,he->bse", jax.nn.silu(g) * u,
                       p["down"].astype(dt))
    return h if cache is None else (h, kc, vc)


@register_lowering(OpType.PIPELINE)
def _pipeline(attrs, inputs, params, ctx):
    (x,) = inputs
    mesh = ctx.mesh
    pipe_deg = 1
    if mesh is not None and "pipe" in mesh.axis_names:
        pipe_deg = dict(zip(mesh.axis_names, mesh.devices.shape))["pipe"]

    if ctx.kv_cache is not None:
        # autoregressive decode: scan the layer stack threading each
        # layer's (b, maxlen, kv, hd) cache slice; caches are stacked on
        # a leading layer dim. Decode always takes the scan path — with
        # pipe-sharded weights GSPMD gathers each layer's slice, which is
        # correct (a real pipe decode schedule would stream tokens; one
        # token at a time has no microbatches to pipeline).
        pos = ctx.cache_position

        def body(carry, xs):
            p, ck, cv = xs
            h, kc, vc = _decoder_block(p, carry, attrs, cache=(ck, cv, pos))
            return h, (kc, vc)

        # the layered decode cache shares the "k"/"v" key convention with
        # the paged pool but is never quantized — no scale sidecar exists
        ck_all = ctx.kv_cache["k"]  # fflint: dtype-ok (fp layered cache)
        cv_all = ctx.kv_cache["v"]  # fflint: dtype-ok (fp layered cache)
        h, (kcs, vcs) = lax.scan(body, x, (params, ck_all, cv_all))
        ctx.cache_updates["k"] = kcs
        ctx.cache_updates["v"] = vcs
        return [h]

    # GPipe only when the node's ASSIGNED view pipe-shards the stacked
    # weights — a default-DP view was priced as a plain scan and must run
    # as one (dispatching on the mesh alone would pay an unpriced bubble)
    view = ctx.sharding
    ln1 = view.weight_specs.get("ln1") if view is not None else None
    pipe_view = bool(ln1 and ln1[0] and "pipe" in ln1[0])

    def scan_layers(h, layer_params, block_mesh=None):
        def body(carry, p):
            return _decoder_block(p, carry, attrs, mesh=block_mesh), None

        out, _ = lax.scan(body, h, layer_params)
        return out

    micro = max(attrs.n_microbatches, 1)
    if (pipe_deg > 1 and pipe_view and attrs.layers % pipe_deg == 0
            and x.shape[0] % micro == 0):
        from flexflow_tpu.parallel.pipeline import pipeline_apply

        per = attrs.layers // pipe_deg
        stacked = jax.tree.map(
            lambda a: a.reshape(pipe_deg, per, *a.shape[1:]), params
        )
        y = pipeline_apply(
            # inside the shard_map worker everything is device-local:
            # the block must NOT re-enter the mesh-aware flash dispatch
            lambda p, h: scan_layers(h, p, block_mesh=None),
            stacked, x, mesh=mesh,
            n_microbatches=micro, axis="pipe",
        )
        return [y]
    # no pipe axis: layer-stacked scan (one compiled block instead of L)
    return [scan_layers(x, params, block_mesh=mesh)]
