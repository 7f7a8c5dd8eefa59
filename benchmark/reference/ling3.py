"""Ling-3.0-flash's language model (the text part of `Ling-3.0-flash-VL`),
written down plainly: float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, no kernels, no cache, no
batching. It imports nothing from the program.

The published `config.json` comes without modelling code, so the
equations are those of the papers its keys name (Kimi Linear / KDA,
arXiv:2510.26692, and the `fla` reference; DeepSeek-V2/V3 for the latent
attention and the router), as ISSUE 44 sets them out. On x (S, E), with
pre-norm residual blocks:

* A layer's attention is SOFTMAX (latent, MLA) iff (i + 1) %
  `layer_group_size` == 0 for its PUBLISHED index i, else LINEAR (KDA).
  Here the kind is the type of the layer's weights (`Kda` / `Mla`).
* KDA on h = RMSNorm(x), H heads of d_k = d_v = 128: q~, k~, v~ = h W_q,
  h W_k, h W_v; each channel through a causal depthwise convolution of
  `short_conv_kernel_size` taps over time (tap K-1 on the token itself,
  zeros before the sequence), then SiLU; per head q = l2norm(q) d_k^-1/2,
  k = l2norm(k); log-decay a_t = `kda_lower_bound` * sigmoid(exp(A_log_h)
  (h W_f + dt_bias)), one value a head and channel, in (-5, 0), alpha_t =
  exp(a_t) (the bounded form `kda_safe_gate` names); beta_t =
  sigmoid(h w_beta,h);

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

  S_0 = 0 in R^{128 x 128} a head, a plain `lax.scan` over tokens;
  o_t = S_t^T q_t; y = [RMSNorm_128(o_t,h) * sigmoid(h W_g)_h] W_o. No rope.
* MLA on h = RMSNorm(x): q = h W_q (H x 192, no low-rank step) = [nope 128
  | rope 64]; [c_kv | k_r] = h W_dkv, c_kv <- RMSNorm(c_kv); plain rope
  (theta 6e6, halves rotated: pair (i, i + 32)) on q_rope and k_r;
  [k_nope_h | v_h] = c_kv W_ukv; scores (q_nope . k_nope + q_rope . k_r)
  192^-1/2, causal softmax; the head's output times sigmoid(h w_gate,h),
  one scalar a head; W_o. The NAIVE form: per-head K and V are expanded.
* Layers before `first_k_dense_replace`: SwiGLU of `intermediate_size`.
  The others: s = sigmoid(h W_r) over ALL routed experts; selection on
  s + bias: `n_group` groups, a group's score the sum of its two largest,
  the `topk_group` best groups kept, the `num_experts_per_tok` largest
  within them; weights the UNBIASED s of the chosen, normalised to sum 1,
  times `routed_scaling_factor`; x + sum over the token's chosen experts
  HELD here of w_e E_e(h) + E_shared(h). What experts not held would add
  is left out (the chip's share of a stated deployment).
* final RMSNorm, untied head over the held vocabulary slice.

Two devices keep a 33 k-row sequence inside one chip's memory and change
no sum: the latent attention takes QUERY_BLOCK query rows at a time, and
the weights arrive as the program stores them (bfloat16 leaves) and are
upcast one layer, one expert at a time.
"""

from __future__ import annotations

from typing import List, NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np


class Kda(NamedTuple):
    wq: jax.Array        # (E, H * d_k)
    wk: jax.Array        # (E, H * d_k)
    wv: jax.Array        # (E, H * d_v)
    conv_q: jax.Array    # (K, H * d_k): tap K - 1 multiplies the token itself
    conv_k: jax.Array
    conv_v: jax.Array
    w_f: jax.Array       # (E, H * d_k)
    dt_bias: jax.Array   # (H * d_k,)
    a_log: jax.Array     # (H,)
    w_beta: jax.Array    # (E, H)
    w_g: jax.Array       # (E, H * d_v)
    o_norm: jax.Array    # (d_v,)
    wo: jax.Array        # (H * d_v, E)


class Mla(NamedTuple):
    wq: jax.Array        # (E, H, nope + rope)
    w_dkv: jax.Array     # (E, kv_lora_rank + rope)
    kv_norm: jax.Array   # (kv_lora_rank,)
    w_ukv: jax.Array     # (kv_lora_rank, H, nope + v)
    w_gate: jax.Array    # (E, H)
    wo: jax.Array        # (H, v, E)


class Dense(NamedTuple):
    gate: jax.Array      # (E, F)
    up: jax.Array
    down: jax.Array      # (F, E)


class Moe(NamedTuple):
    router: jax.Array    # (E, num_experts published)
    bias: jax.Array      # (num_experts published,): selection only
    w_gate: jax.Array    # (held, E, F)
    w_up: jax.Array
    w_down: jax.Array    # (held, F, E)
    shared_gate: jax.Array  # (E, Fs)
    shared_up: jax.Array
    shared_down: jax.Array  # (Fs, E)


class Layer(NamedTuple):
    attn_norm: jax.Array
    attn: Union[Kda, Mla]
    mlp_norm: jax.Array
    mlp: Union[Dense, Moe]


class Weights(NamedTuple):
    embed: jax.Array       # (V, E)
    layers: List[Layer]
    final_norm: jax.Array  # (E,)
    head: jax.Array        # (E, V)


class Arch(NamedTuple):
    """What the equations need of the configuration file."""

    heads: int
    kda_head_dim: int
    kda_lower_bound: float
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    rope_theta: float
    experts_per_tok: int
    n_group: int
    topk_group: int
    held_lo: int
    held_hi: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float


QUERY_BLOCK = 256
L2_EPS = 1e-6
F32 = jnp.float32


def lower_precision(dtype):
    """`(array) -> array` that rounds to `dtype` and comes back to float32:
    the reference "computed in a lower precision" for calibrating the
    cell's tolerance (`benchmark/reference/ling3_precision.py`), where
    every matrix product's two operands are rounded first. None is the
    reference itself."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def short_conv(x, taps):
    """x (S, C), taps (K, C): y_t = silu(sum_j taps[j] x_{t - (K-1) + j}),
    zeros before the sequence."""
    s, k = x.shape[0], taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    y = sum(taps[j].astype(F32) * xp[j:j + s] for j in range(k))
    return jax.nn.silu(y)


def delta_rule(q, k, v, a, beta):
    """The recurrence, a token at a time. q, k, a (S, H, d_k), v (S, H,
    d_v), beta (S, H) -> o (S, H, d_v); the state (H, d_k, d_v) float32
    starts at zero."""

    def step(st, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        st = st * jnp.exp(a_t)[:, :, None]
        u = jnp.einsum("hk,hkv->hv", k_t, st)
        st = st + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - u))
        return st, jnp.einsum("hk,hkv->hv", q_t, st)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, zero, (q, k, v, a, beta))[1]


def _kda(h, w: Kda, a: Arch, r):
    s, H, d = h.shape[0], a.heads, a.kda_head_dim
    h = r(h)
    q = short_conv(h @ r(w.wq.astype(F32)), w.conv_q).reshape(s, H, d)
    k = short_conv(h @ r(w.wk.astype(F32)), w.conv_k).reshape(s, H, d)
    v = short_conv(h @ r(w.wv.astype(F32)), w.conv_v).reshape(s, H, -1)
    q = _l2_norm(q) * d ** -0.5
    k = _l2_norm(k)
    f = (h @ r(w.w_f.astype(F32)) + w.dt_bias.astype(F32)).reshape(s, H, d)
    log_decay = a.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(w.a_log.astype(F32))[None, :, None] * f)
    beta = jax.nn.sigmoid(h @ r(w.w_beta.astype(F32)))
    o = delta_rule(q, k, v, log_decay, beta)
    o = _rms_norm(o, w.o_norm, a.rms_norm_eps) * jax.nn.sigmoid(
        h @ r(w.w_g.astype(F32))).reshape(o.shape)
    return r(o.reshape(s, -1)) @ r(w.wo.astype(F32))


def _rope(x, a: Arch):
    """x: (S, ..., d): position s turns pair (i, i + d/2) by s theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = a.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(h, w: Mla, a: Arch, r):
    s = h.shape[0]
    n, c = a.qk_nope_head_dim, a.kv_lora_rank
    h = r(h)
    q = jnp.einsum("se,ehd->shd", h, r(w.wq.astype(F32)))
    kv = h @ r(w.w_dkv.astype(F32))
    c_kv = _rms_norm(kv[:, :c], w.kv_norm, a.rms_norm_eps)
    k_r = _rope(kv[:, c:], a)                                   # (S, r)
    kvh = jnp.einsum("sc,chd->shd", r(c_kv), r(w.w_ukv.astype(F32)))
    k_nope, v = r(kvh[..., :n]), r(kvh[..., n:])
    k_r = r(k_r)
    q_nope, q_rope = q[..., :n], _rope(q[..., n:], a)
    scale = (n + a.qk_rope_head_dim) ** -0.5
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(args):
        start, qn, qr = args
        sc = (jnp.einsum("shd,thd->hst", r(qn), k_nope)
              + jnp.einsum("shd,td->hst", r(qr), k_r)) * scale
        seen = (start + jnp.arange(block))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", r(p), v)

    nb = s // block
    o = jax.lax.map(rows, (jnp.arange(0, s, block),
                           q_nope.reshape((nb, block) + q_nope.shape[1:]),
                           q_rope.reshape((nb, block) + q_rope.shape[1:])))
    o = o.reshape(s, a.heads, -1) * jax.nn.sigmoid(
        h @ r(w.w_gate.astype(F32)))[:, :, None]
    return jnp.einsum("shd,hde->se", r(o), r(w.wo.astype(F32)))


def _swiglu(h, gate, up, down, r=lower_precision(None)):
    return r(jax.nn.silu(h @ r(gate.astype(F32))) * (h @ r(up.astype(F32)))
             ) @ r(down.astype(F32))


def route(h, w: Moe, a: Arch):
    """(ids (S, k) over all routed experts, weights (S, k))."""
    s = jax.nn.sigmoid(h @ w.router.astype(F32))
    biased = s + w.bias.astype(F32)
    n, g = s.shape
    groups = biased.reshape(n, a.n_group, g // a.n_group)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, a.topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(a.n_group), axis=1)
    open_ = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(n, g)
    _, ids = jax.lax.top_k(open_, a.experts_per_tok)
    wt = jnp.take_along_axis(s, ids, axis=-1)
    if a.norm_topk_prob:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    return ids, wt * a.routed_scaling_factor


def _experts(h, w: Moe, a: Arch, r=lower_precision(None), routes=None):
    h = r(h)
    ids, wt = route(h, w, a)
    if routes is not None:
        routes.append(ids)
    held = jnp.arange(a.held_lo, a.held_hi)
    # (S, held): the token's weight for each held expert, 0 if not chosen
    per = jnp.sum(jnp.where(ids[:, :, None] == held[None, None, :],
                            wt[:, :, None], 0.0), axis=1)

    def one(g, acc):
        return acc + per[:, g, None] * _swiglu(h, w.w_gate[g], w.w_up[g],
                                               w.w_down[g], r)

    routed = jax.lax.fori_loop(0, a.held_hi - a.held_lo, one,
                               jnp.zeros_like(h))
    return routed + _swiglu(h, w.shared_gate, w.shared_up, w.shared_down, r)


def layer(x, lyr: Layer, a: Arch, r=lower_precision(None), routes=None):
    """One block on x (S, E) float32."""
    h = _rms_norm(x, lyr.attn_norm, a.rms_norm_eps)
    x = x + (_kda if isinstance(lyr.attn, Kda) else _mla)(h, lyr.attn, a, r)
    h = _rms_norm(x, lyr.mlp_norm, a.rms_norm_eps)
    if isinstance(lyr.mlp, Dense):
        return x + _swiglu(r(h), lyr.mlp.gate, lyr.mlp.up, lyr.mlp.down, r)
    return x + _experts(h, lyr.mlp, a, r, routes)


def logits(w: Weights, ids, *, arch: Arch, operand_dtype=None, routes=None):
    """ids: (S,) int32 -> (S, V) float32 logits of one sequence. With
    `operand_dtype` every matrix product's operands are first rounded to
    it (`lower_precision`; the router's product and the recurrence stay
    float32, as the program's do): NOT the reference, a yardstick for its
    tolerance. `routes`, a list, collects each expert layer's (S, k)
    chosen experts."""
    r = lower_precision(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = w.embed[ids].astype(F32)
        for lyr in w.layers:
            x = layer(x, lyr, arch, r, routes)
        x = _rms_norm(x, w.final_norm, arch.rms_norm_eps)
        return r(x) @ r(w.head.astype(F32))
