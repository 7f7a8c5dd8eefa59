"""Multi-head latent attention (LatentAttentionAttrs): the projections,
YaRN rope on interleaved pairs, the position scale on q, and the two
forms of the attention itself.

NAIVE form (dense lowering: forward, training, the test oracle): expand
`[k_nope_h | v_h] = c_kv W_ukv` for every head and attend per head.

ABSORBED form (paged lowering): fold W_uk into the query and W_uv into
the output, so that every head attends over the cached row itself,

    q~_h = [q_nope_h W_uk,h^T | q_rope_h]      score = q~_h . [c_kv | k_r]
    o~_h = sum p c_kv                          o_h   = o~_h W_uv,h

which is what lets the page pool hold one `[c_kv | k_r]` row a token
(paged/latent.py). The two are the same mathematics in another order of
multiplication; tests/test_mistral4.py pins them to each other.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(attrs) -> np.ndarray:
    """(rope_dim / 2,) float32 rotary frequencies. Plain rope when
    `rope_factor` is 1; otherwise YaRN's blend: below the correction dim
    of `beta_fast` the published frequency (extrapolation), above that
    of `beta_slow` the frequency over `rope_factor` (interpolation), a
    linear ramp between them."""
    d = attrs.qk_rope_head_dim
    pos_freqs = attrs.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extra = 1.0 / pos_freqs
    if attrs.rope_factor == 1.0 or not attrs.rope_original_max:
        return extra.astype(np.float32)
    inter = 1.0 / (attrs.rope_factor * pos_freqs)

    def correction_dim(rotations: float) -> float:
        return (d * math.log(attrs.rope_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(attrs.rope_theta)))

    low = max(math.floor(correction_dim(attrs.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(attrs.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope(x, positions, attrs):
    """Rotate the last dim of x ((B, S, ..., d)) at `positions` (B, S).
    With `rope_interleave` the pairs are (2i, 2i+1): the dim is first
    brought to half-split order (evens, then odds) and rotated there, so
    the result is a fixed permutation of the interleaved rotation. Every
    roped vector (q_rope of each head and k_r) goes through this one
    function, and a dot product does not see a permutation both sides
    share. Angles in float32, rotation in x's dtype (ops/jax_ops.py
    apply_rope says why)."""
    d = x.shape[-1]
    if attrs.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(attrs))                               # (B, S, d/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def query_scale(attrs, positions):
    """(B, S) float32: the softmax scale times the position scale on q,
    1 + beta * ln(1 + floor(pos / original_max))."""
    scale = jnp.full(positions.shape, attrs.softmax_scale, jnp.float32)
    if attrs.q_scale_beta and attrs.rope_original_max:
        scale = scale * (1.0 + attrs.q_scale_beta * jnp.log1p(jnp.floor(
            positions.astype(jnp.float32) / attrs.rope_original_max)))
    return scale


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def project(attrs, x, params, positions):
    """x: (B, S, E) -> (q_nope (B,S,H,n), q_rope (B,S,H,r) roped and
    scaled, c_kv (B,S,c) normed, k_r (B,S,r) roped); q_nope carries the
    query scale too."""
    B, S, _ = x.shape
    H, n = attrs.num_heads, attrs.qk_nope_head_dim
    if attrs.q_lora_rank is None:
        c_q = x
    else:
        c_q = _rms(_dot(x, params["w_dq"]), params["q_norm"],
                   attrs.norm_eps)
    q = _dot(c_q, params["w_uq"].reshape(c_q.shape[-1], -1))
    q = q.reshape(B, S, H, attrs.qk_head_dim)
    qs = query_scale(attrs, positions)[:, :, None, None]
    q_nope = (q[..., :n].astype(jnp.float32) * qs).astype(x.dtype)
    q_rope = (rope(q[..., n:], positions, attrs).astype(jnp.float32)
              * qs).astype(x.dtype)
    kv = _dot(x, params["w_dkv"])
    c_kv = _rms(kv[..., :attrs.kv_lora_rank], params["kv_norm"],
                attrs.norm_eps)
    k_r = rope(kv[..., attrs.kv_lora_rank:], positions, attrs)
    return q_nope, q_rope, c_kv, k_r


def output(attrs, o, params, x):
    """(B, S, H, v) head outputs -> (B, S, E) through W_o; with
    `out_gate` each head's output first times sigmoid(x w_gate,h)."""
    B, S = o.shape[:2]
    if attrs.out_gate:
        gate = jax.nn.sigmoid(jnp.dot(
            x, params["w_gate"].astype(x.dtype),
            preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
    return _dot(o.reshape(B, S, -1), params["wo"].reshape(-1, attrs.embed_dim))


def naive_attention(attrs, x, params):
    """Causal attention of the whole sequence, per-head K and V expanded
    from c_kv (the dense lowering)."""
    B, S, _ = x.shape
    n = attrs.qk_nope_head_dim
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_rope, c_kv, k_r = project(attrs, x, params, positions)
    kv = jnp.einsum("bsc,chd->bshd", c_kv, params["w_ukv"].astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    k_nope, v = kv[..., :n], kv[..., n:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r,
                      preferred_element_type=jnp.float32))
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(x.dtype), v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return output(attrs, o, params, x)


def absorbed_queries(attrs, q_nope, q_rope, params):
    """(B, S, H, latent_width): [q_nope_h W_uk,h^T | q_rope_h]."""
    w_uk = params["w_ukv"][..., :attrs.qk_nope_head_dim]     # (c, H, n)
    q_abs = jnp.einsum("bshn,chn->bshc", q_nope, w_uk.astype(q_nope.dtype),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_abs.astype(q_nope.dtype), q_rope], axis=-1)


def absorbed_output(attrs, o_lat, params, x):
    """(B, S, H, c) latent outputs -> (B, S, E): W_uv per head, then W_o."""
    w_uv = params["w_ukv"][..., attrs.qk_nope_head_dim:]     # (c, H, v)
    o = jnp.einsum("bshc,chv->bshv", o_lat, w_uv.astype(o_lat.dtype),
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return output(attrs, o, params, x)


def paged_attention(attrs, x, params, ctx):
    """The paged step (decode, chunk, tree verify alike): rows at
    pos + depths, the tokens' latent rows appended to the pool, absorbed
    attention over the page table. Returns (y, new pool)."""
    from flexflow_tpu.paged.latent import latent_paged_attention

    positions = jnp.asarray(ctx.cache_position)[:, None] + ctx.ragged_depths
    q_nope, q_rope, c_kv, k_r = project(attrs, x, params, positions)
    q = absorbed_queries(attrs, q_nope, q_rope, params)
    row = jnp.concatenate([c_kv, k_r], axis=-1)
    o_lat, pool = latent_paged_attention(
        q, row, ctx.kv_cache["c"], ctx.page_tables, ctx.cache_position,
        ctx.ragged_q_lens, ctx.ragged_anc, value_width=attrs.kv_lora_rank)
    return absorbed_output(attrs, o_lat, params, x), pool
