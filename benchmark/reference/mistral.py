"""Mistral-7B's forward pass, written down plainly.

Follows the published description (Jiang et al. 2023, "Mistral 7B";
`modeling_mistral.py` of `transformers`): token embedding; per layer
RMSNorm -> grouped-query attention with rotary embeddings in the half-split
(`rotate_half`) convention and a causal mask -> residual, RMSNorm -> SwiGLU
MLP (`down(silu(gate(x)) * up(x))`) -> residual; final RMSNorm; an untied
output head. v0.3 has no sliding window. Everything is float32 under
`jax.default_matmul_precision("highest")`, because on a TPU a float32 matrix
multiplication otherwise runs in bfloat16 passes.

Departure from the publication: none in the mathematics. The weights arrive
in the layout the program keeps them in (`wq` as (hidden, heads, head_dim),
`wo` as (heads, head_dim, hidden), dense kernels as (in, out)); the family
module turns the program's parameter tree into this module's `Weights`.
"""

from __future__ import annotations

from typing import List, NamedTuple

import jax
import jax.numpy as jnp


class Layer(NamedTuple):
    attn_norm: jax.Array   # (E,)
    wq: jax.Array          # (E, H, D)
    wk: jax.Array          # (E, Hkv, D)
    wv: jax.Array          # (E, Hkv, D)
    wo: jax.Array          # (H, D, E)
    mlp_norm: jax.Array    # (E,)
    gate: jax.Array        # (E, F)
    up: jax.Array          # (E, F)
    down: jax.Array        # (F, E)


class Weights(NamedTuple):
    embed: jax.Array       # (V, E)
    layers: List[Layer]
    final_norm: jax.Array  # (E,)
    head: jax.Array        # (E, V)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x: (S, H, D); position s rotates pair (i, i + D/2) by s * theta^(-2i/D)."""
    s, _h, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


QUERY_BLOCK = 512


def _causal_attention(q, k, v):
    """softmax(q k^T / sqrt(D), causal) v for (S, H, D) arrays. The same
    sums as the one-line form, taken QUERY_BLOCK query rows at a time so
    that a 4k-token sequence does not hold an H x S x S score array (1.8 GB
    at S = 3712) beside the served model's weights on one chip."""
    s, h, d = q.shape
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(args):
        start, qb = args
        sc = jnp.einsum("shd,thd->hst", qb, k) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(block))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", p, v)

    starts = jnp.arange(0, s, block)
    out = jax.lax.map(rows, (starts, q.reshape(s // block, block, h, d)))
    return out.reshape(s, h, d)


def logits(w: Weights, ids, *, rope_theta: float, rms_norm_eps: float):
    """ids: (S,) int32 -> (S, V) float32 logits of one sequence."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        h = w.embed.astype(f32)[ids]
        for lyr in w.layers:
            x = _rms_norm(h, lyr.attn_norm.astype(f32), rms_norm_eps)
            q = jnp.einsum("se,ehd->shd", x, lyr.wq.astype(f32))
            k = jnp.einsum("se,ehd->shd", x, lyr.wk.astype(f32))
            v = jnp.einsum("se,ehd->shd", x, lyr.wv.astype(f32))
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            rep = q.shape[1] // k.shape[1]
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            o = _causal_attention(q, k, v)
            h = h + jnp.einsum("shd,hde->se", o, lyr.wo.astype(f32))
            x = _rms_norm(h, lyr.mlp_norm.astype(f32), rms_norm_eps)
            g = x @ lyr.gate.astype(f32)
            u = x @ lyr.up.astype(f32)
            h = h + (jax.nn.silu(g) * u) @ lyr.down.astype(f32)
        h = _rms_norm(h, w.final_norm.astype(f32), rms_norm_eps)
        return h @ w.head.astype(f32)


def sequence_loss(w: Weights, ids, labels, *, rope_theta: float,
                  rms_norm_eps: float):
    """Mean over positions of -log softmax(logits(ids))[labels]."""
    lg = logits(w, ids, rope_theta=rope_theta, rms_norm_eps=rms_norm_eps)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
