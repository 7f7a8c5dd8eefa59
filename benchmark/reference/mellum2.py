"""Mellum-2's language model (`model_type` mellum), written down plainly:
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching.

Per layer l, on x (S, E), as ISSUE 36 sets the equations out from the
published config's keys:

* attention on h = RMSNorm(x): q = h Wq (H heads x D), k = h Wk, v = h Wv
  (Hkv heads x D), no bias, no per-head normalisation. Rope on q and k in
  the half-split ("rotate_half") pair layout, by `layer_types[l]`: a
  `sliding_attention` layer turns pair i by pos * theta^(-2i/D), cos and
  sin unscaled; a `full_attention` layer by YaRN's blended frequencies
  (`yarn_inv_freq`), cos and sin both times `attention_factor`. Scores
  q k^T / sqrt(D), q head h on kv head h // (H / Hkv); key j is visible to
  query i iff j <= i, and in a sliding layer also i - j < sliding_window.
  Softmax in float32, o = P v, x += o Wo.
* experts on h = RMSNorm(x): p = softmax(h Wr) over ALL routed experts,
  the `num_experts_per_tok` largest, renormalised to sum 1
  (`norm_topk_prob`); x += sum over those of them HELD here of p_e
  (silu(h Wg_e) * (h Wu_e)) Wd_e. Every held expert is computed for every
  token, as a dense loop, and weighted by zero where the token did not
  choose it. What experts not held would add is left out (the chip's
  share of a stated deployment, model-configs guide section 4). No shared
  expert.
* final RMSNorm, untied head over the held vocabulary slice.

Departures from that text: none in the mathematics. Two devices keep the
computation inside one chip's memory beside the served model's weights at
33,280 rows, and change no sum: attention takes QUERY_BLOCK query rows at
a time (a (32, 33280, 33280) float32 score array is 142 GB), and the
weights arrive as the program stores them (bfloat16 leaves) and are
upcast to float32 one layer, one expert at a time inside a loop.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Layer(NamedTuple):
    attn_norm: jax.Array   # (E,)
    wq: jax.Array          # (E, H, D)
    wk: jax.Array          # (E, Hkv, D)
    wv: jax.Array          # (E, Hkv, D)
    wo: jax.Array          # (H, D, E)
    moe_norm: jax.Array    # (E,)
    router: jax.Array      # (E, num_experts published)
    w_gate: jax.Array      # (held, E, F)
    w_up: jax.Array        # (held, E, F)
    w_down: jax.Array      # (held, F, E)


class Weights(NamedTuple):
    embed: jax.Array       # (V, E)
    layers: List[Layer]
    final_norm: jax.Array  # (E,)
    head: jax.Array        # (E, V)


class Arch(NamedTuple):
    """What the equations need of the configuration file."""

    layer_sliding: Tuple[bool, ...]    # one a layer: sliding_attention?
    sliding_window: int
    experts_per_tok: int
    held_lo: int
    held_hi: int
    norm_topk_prob: bool
    rms_norm_eps: float
    sliding_theta: float
    full_theta: float
    full_factor: float
    full_original_max: int
    full_beta_fast: float
    full_beta_slow: float
    full_attention_factor: float


QUERY_BLOCK = 256
F32 = jnp.float32


def lower_precision(dtype):
    """`(array) -> array` that rounds to `dtype` and comes back to float32:
    the reference "computed in a lower precision" for calibrating the
    cell's tolerance (`benchmark/reference/mellum2_precision.py`), where
    every matrix product's two operands are rounded first. None is the
    reference itself."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def yarn_inv_freq(a: Arch, d: int) -> np.ndarray:
    """extrap_i = theta^(-2i/d), interp_i = extrap_i / factor; between the
    correction dims low = floor((d/2) ln(L / (beta_fast 2 pi)) / ln theta)
    and high = ceil((d/2) ln(L / (beta_slow 2 pi)) / ln theta), clipped to
    [0, d/2 - 1], a linear ramp from the first to the second."""
    half = d // 2
    extrap = a.full_theta ** (-np.arange(half, dtype=np.float64) / half)

    def dim_of(turns):
        return (half * math.log(a.full_original_max / (turns * 2 * math.pi))
                / math.log(a.full_theta))

    low = max(math.floor(dim_of(a.full_beta_fast)), 0)
    high = min(math.ceil(dim_of(a.full_beta_slow)), half - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return extrap / a.full_factor * ramp + extrap * (1.0 - ramp)


def _rope(x, inv_freq, factor: float):
    """x: (S, heads, d): position s turns the pair (i, i + d/2) by
    s * inv_freq_i; cos and sin are both multiplied by `factor`."""
    s, d = x.shape[0], x.shape[-1]
    ang = (jnp.arange(s, dtype=F32)[:, None]
           * jnp.asarray(inv_freq, F32)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _attention(h, lyr: Layer, a: Arch, sliding: bool, r):
    s = h.shape[0]
    h = r(h)
    q = jnp.einsum("se,ehd->shd", h, r(lyr.wq.astype(F32)))
    k = jnp.einsum("se,ehd->shd", h, r(lyr.wk.astype(F32)))
    v = jnp.einsum("se,ehd->shd", h, r(lyr.wv.astype(F32)))
    heads, d = q.shape[1], q.shape[2]
    rep = heads // k.shape[1]
    if sliding:
        half = d // 2
        inv = a.sliding_theta ** (-np.arange(half, dtype=np.float64) / half)
        q, k = _rope(q, inv, 1.0), _rope(k, inv, 1.0)
    else:
        inv = yarn_inv_freq(a, d)
        q = _rope(q, inv, a.full_attention_factor)
        k = _rope(k, inv, a.full_attention_factor)
    k = r(jnp.repeat(k, rep, axis=1))
    v = r(jnp.repeat(v, rep, axis=1))
    scale = d ** -0.5
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(args):
        start, qb = args
        sc = jnp.einsum("shd,thd->hst", r(qb), k) * scale
        i = (start + jnp.arange(block))[:, None]
        seen = cols[None, :] <= i
        if sliding:
            seen &= i - cols[None, :] < a.sliding_window
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", r(p), v)

    nb = s // block
    o = jax.lax.map(rows, (jnp.arange(0, s, block),
                           q.reshape((nb, block) + q.shape[1:])))
    return jnp.einsum("shd,hde->se", r(o.reshape(s, heads, d)),
                      r(lyr.wo.astype(F32)))


def _swiglu(h, gate, up, down, r):
    return r(jax.nn.silu(h @ r(gate.astype(F32))) * (h @ r(up.astype(F32)))
             ) @ r(down.astype(F32))


def route(h, lyr: Layer, a: Arch):
    """(ids (S, k) over all routed experts, weights (S, k))."""
    p = jax.nn.softmax(h @ lyr.router.astype(F32), axis=-1)
    w, ids = jax.lax.top_k(p, a.experts_per_tok)
    if a.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w


def _experts(h, lyr: Layer, a: Arch, r, routes=None):
    h = r(h)
    ids, w = route(h, lyr, a)
    if routes is not None:
        routes.append(ids)
    held = jnp.arange(a.held_lo, a.held_hi)
    # (S, held): the token's weight for each held expert, 0 if not chosen
    wt = jnp.sum(jnp.where(ids[:, :, None] == held[None, None, :],
                           w[:, :, None], 0.0), axis=1)

    def one(g, acc):
        return acc + wt[:, g, None] * _swiglu(h, lyr.w_gate[g],
                                              lyr.w_up[g], lyr.w_down[g], r)

    return jax.lax.fori_loop(0, a.held_hi - a.held_lo, one,
                             jnp.zeros_like(h))


def layer(x, lyr: Layer, a: Arch, sliding: bool, r=lower_precision(None),
          routes=None):
    """One block on x (S, E) float32."""
    x = x + _attention(_rms_norm(x, lyr.attn_norm, a.rms_norm_eps), lyr, a,
                       sliding, r)
    return x + _experts(_rms_norm(x, lyr.moe_norm, a.rms_norm_eps), lyr, a,
                        r, routes)


def logits(w: Weights, ids, *, arch: Arch, operand_dtype=None, routes=None):
    """ids: (S,) int32 -> (S, V) float32 logits of one sequence. With
    `operand_dtype` every matrix product's operands are first rounded to
    it (`lower_precision`; the router's product stays float32, as the
    program's does): NOT the reference, a yardstick for its tolerance.
    `routes`, a list, collects each layer's (S, k) chosen experts."""
    r = lower_precision(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = w.embed[ids].astype(F32)
        for lyr, sliding in zip(w.layers, arch.layer_sliding):
            x = layer(x, lyr, arch, sliding, r, routes)
        x = _rms_norm(x, w.final_norm, arch.rms_norm_eps)
        return r(x) @ r(w.head.astype(F32))
