"""Mistral-Small-4's language model (`model_type` mistral4), written down
plainly: float32 `jax.numpy` under `jax.default_matmul_precision("highest")`,
no kernels, no cache, no batching.

Per layer, on x (S, E), as ISSUE 27 sets the equations out from the
published config's keys (the DeepSeek-V3 / Llama-4 conventions they name):

* attention on h = RMSNorm(x): c_q = RMSNorm(h W_dq); q = c_q W_uq, per
  head [q_nope | q_rope]; [c_kv | k_r] = h W_dkv, c_kv <- RMSNorm(c_kv);
  per head [k_nope_h | v_h] = c_kv W_ukv. Rope on q_rope_h and on k_r (one
  k_r for all heads), on INTERLEAVED pairs (2i, 2i+1), with YaRN
  frequencies (`_yarn_inv_freq`); cos / sin are not rescaled (mscale =
  mscale_all_dim). q is multiplied by 1 + beta ln(1 + floor(pos /
  original_max_position_embeddings)) (`llama_4_scaling_beta`). Scores
  (q_nope_h . k_nope_h + q_rope_h . k_r) * qk_head_dim^-0.5 * (0.1
  mscale_all_dim ln factor + 1)^2, causal softmax, o_h = sum p v_h, then
  W_o and the residual. The NAIVE form: per-head K and V are expanded.
* experts on h = RMSNorm(x): p = softmax(h W_g) over ALL routed experts,
  the `num_experts_per_tok` largest, renormalised to sum 1
  (`norm_topk_prob`) and times `routed_scaling_factor`; x + sum over the
  token's chosen experts that are HELD here of w_e E_e(h), + E_shared(h),
  E(h) = (silu(h W_gate) * h W_up) W_down. Every held expert is computed
  for every token, as a dense loop, and weighted by zero where the token
  did not choose it. What experts not held would add is left out (the
  chip's share of a stated deployment, model-configs guide section 4).
* final RMSNorm, untied head over the held vocabulary slice.

Departures from that text: none in the mathematics. Two devices keep the
computation inside one chip's memory beside the served model's weights
at 12,800 rows, and change no sum: attention takes QUERY_BLOCK query rows
at a time (a (32, 12800, 12800) float32 score array is 21 GB), and the
weights arrive as the program stores them (bfloat16 leaves) and are
upcast to float32 one layer, one expert at a time inside a loop (all 32
held experts of a layer in float32 are 3.2 GB, their activations at
12,800 rows 3.4 GB).
"""

from __future__ import annotations

import math
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Layer(NamedTuple):
    attn_norm: jax.Array   # (E,)
    w_dq: jax.Array        # (E, q_lora_rank)
    q_norm: jax.Array      # (q_lora_rank,)
    w_uq: jax.Array        # (q_lora_rank, H, nope + rope)
    w_dkv: jax.Array       # (E, kv_lora_rank + rope)
    kv_norm: jax.Array     # (kv_lora_rank,)
    w_ukv: jax.Array       # (kv_lora_rank, H, nope + v)
    wo: jax.Array          # (H, v, E)
    moe_norm: jax.Array    # (E,)
    router: jax.Array      # (E, n_routed_experts published)
    w_gate: jax.Array      # (held, E, F)
    w_up: jax.Array        # (held, E, F)
    w_down: jax.Array      # (held, F, E)
    shared_gate: jax.Array  # (E, Fs)
    shared_up: jax.Array    # (E, Fs)
    shared_down: jax.Array  # (Fs, E)


class Weights(NamedTuple):
    embed: jax.Array       # (V, E)
    layers: List[Layer]
    final_norm: jax.Array  # (E,)
    head: jax.Array        # (E, V)


class Arch(NamedTuple):
    """What the equations need of the configuration file."""

    heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    experts_per_tok: int
    held_lo: int
    held_hi: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original_max: int
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    llama_4_scaling_beta: float


QUERY_BLOCK = 256
F32 = jnp.float32


def lower_precision(dtype):
    """`(array) -> array` that rounds to `dtype` and comes back to float32:
    the reference "computed in a lower precision" for calibrating the
    cell's tolerance (`benchmark/reference/mistral4_precision.py`), where
    every matrix product's two operands are rounded first. None is the
    reference itself."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def _yarn_inv_freq(a: Arch) -> np.ndarray:
    """YaRN: theta^(-2i/d) where a pair turns more than beta_fast times
    over the original context, the same over `factor` where it turns
    fewer than beta_slow times, a linear ramp between the two correction
    dims d ln(L / (2 pi beta)) / (2 ln theta), floored and ceiled."""
    d = a.qk_rope_head_dim
    base = a.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return (d * math.log(a.rope_original_max / (turns * 2 * math.pi))
                / (2 * math.log(a.rope_theta)))

    low = max(math.floor(dim_of(a.beta_fast)), 0)
    high = min(math.ceil(dim_of(a.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (base * (1.0 - ramp) + base / a.rope_factor * ramp).astype(
        np.float32)


def _rope_interleaved(x, a: Arch):
    """x: (S, ..., d): position s turns pair (2i, 2i+1) by s * inv_freq_i."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(_yarn_inv_freq(a))
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(h, lyr: Layer, a: Arch, r):
    s = h.shape[0]
    n, c = a.qk_nope_head_dim, a.kv_lora_rank
    h = r(h)
    c_q = _rms_norm(h @ r(lyr.w_dq.astype(F32)), lyr.q_norm, a.rms_norm_eps)
    q = jnp.einsum("sr,rhd->shd", r(c_q), r(lyr.w_uq.astype(F32)))
    kv = h @ r(lyr.w_dkv.astype(F32))
    c_kv = _rms_norm(kv[:, :c], lyr.kv_norm, a.rms_norm_eps)
    k_r = _rope_interleaved(kv[:, c:], a)                       # (S, r)
    kvh = jnp.einsum("sc,chd->shd", r(c_kv), r(lyr.w_ukv.astype(F32)))
    k_nope, v = kvh[..., :n], kvh[..., n:]
    pos = jnp.arange(s, dtype=F32)
    q = q * (1.0 + a.llama_4_scaling_beta * jnp.log1p(
        jnp.floor(pos / a.rope_original_max)))[:, None, None]
    q_nope, q_rope = q[..., :n], _rope_interleaved(q[..., n:], a)
    m = 0.1 * a.mscale_all_dim * math.log(a.rope_factor) + 1.0
    scale = (n + a.qk_rope_head_dim) ** -0.5 * m * m
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    k_nope, k_r, v = r(k_nope), r(k_r), r(v)

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
    return jnp.einsum("shd,hde->se", r(o.reshape(s, a.heads, -1)),
                      r(lyr.wo.astype(F32)))


def _swiglu(h, gate, up, down, r=lower_precision(None)):
    return r(jax.nn.silu(h @ r(gate.astype(F32))) * (h @ r(up.astype(F32)))
             ) @ r(down.astype(F32))


def route(h, lyr: Layer, a: Arch):
    """(ids (S, k) over all routed experts, weights (S, k))."""
    p = jax.nn.softmax(h @ lyr.router.astype(F32), axis=-1)
    w, ids = jax.lax.top_k(p, a.experts_per_tok)
    if a.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * a.routed_scaling_factor


def _experts(h, lyr: Layer, a: Arch, r=lower_precision(None), routes=None):
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

    routed = jax.lax.fori_loop(0, a.held_hi - a.held_lo, one,
                               jnp.zeros_like(h))
    return routed + _swiglu(h, lyr.shared_gate, lyr.shared_up,
                            lyr.shared_down, r)


def layer(x, lyr: Layer, a: Arch, r=lower_precision(None), routes=None):
    """One block on x (S, E) float32."""
    x = x + _attention(_rms_norm(x, lyr.attn_norm, a.rms_norm_eps), lyr, a,
                       r)
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
        for lyr in w.layers:
            x = layer(x, lyr, arch, r, routes)
        x = _rms_norm(x, w.final_norm, arch.rms_norm_eps)
        return r(x) @ r(w.head.astype(F32))
