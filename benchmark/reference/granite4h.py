"""Granite 4.0-H (`model_type` `granitemoehybrid`; the dense Micro model),
written down plainly: float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, no kernels, no cache, no
batching. It imports nothing from the program.

The equations are those of Hugging Face's `modeling_granitemoehybrid.py`
and of Mamba-2 (arXiv:2405.21060), as ISSUE 51 sets them out. On ids (S,):

* h = `embedding_multiplier` * E[ids].
* A layer: h += `residual_multiplier` * Mixer(RMSNorm(h)), then
  h += `residual_multiplier` * ((silu(a) * b) W_down) with a = RMSNorm(h)
  W_gate, b = RMSNorm(h) W_up (the published `input_linear`, hidden -> 2 x
  `shared_intermediate_size`, held as its two halves). Norm eps
  `rms_norm_eps`. The mixer's kind is the type of the layer's weights
  (`Mamba` / `Attention`), which follows `layer_types`.
* Mamba-2 mixer on u = RMSNorm(h): H heads of P channels, a state of N a
  channel, one group, K taps: [z (H P) | xBC (H P + 2 N) | dt (H)] = u W_in
  (no bias); xBC = silu(conv(xBC) + bias), depthwise, causal, zeros before
  the sequence, tap K - 1 on the token itself; x (H, P), B (N), C (N) =
  split(xBC); D_t,h = softplus(dt_t,h + dt_bias_h) (no clamp:
  `time_step_limit` (0, inf)); a_t,h = -exp(A_log_h) D_t,h;

      S_t,h = exp(a_t,h) S_t-1,h + D_t,h x_t,h B_t^T          (P x N)

  S_0 = 0, a plain `lax.scan` over tokens; y_t,h = S_t,h C_t + Dskip_h
  x_t,h; y = RMSNorm_HP(y * silu(z)) * w (the gate BEFORE the norm, one
  group over all H P channels); out = y W_out.
* Attention on u = RMSNorm(h): q, k, v = u W_q, u W_k, u W_v (no bias), NO
  positional encoding (`position_embedding_type` "nope"), scores q . k *
  `attention_multiplier` (1/64 on heads of 64, not 1/8), causal softmax,
  each kv head shared by heads / kv_heads query heads, W_o.
* logits = RMSNorm(h) E^T / `logits_scaling`: the head IS the embedding.

Two devices keep a 3,072-row sequence through 40 layers and its (3,072,
100,352) float32 logits inside one chip's memory beside the program's
weights, and change no sum: attention takes QUERY_BLOCK query rows at a
time, and the weights arrive as the program stores them (bfloat16 leaves)
and are upcast one layer at a time.
"""

from __future__ import annotations

from typing import List, NamedTuple, Union

import jax
import jax.numpy as jnp


class Mamba(NamedTuple):
    w_in: jax.Array        # (E, H P + (H P + 2 N) + H): z | xBC | dt
    conv: jax.Array        # (K, H P + 2 N): tap K - 1 on the token itself
    conv_bias: jax.Array   # (H P + 2 N,)
    dt_bias: jax.Array     # (H,)
    a_log: jax.Array       # (H,)
    d_skip: jax.Array      # (H,)
    norm: jax.Array        # (H P,)
    w_out: jax.Array       # (H P, E)


class Attention(NamedTuple):
    wq: jax.Array          # (E, H, D)
    wk: jax.Array          # (E, Hkv, D)
    wv: jax.Array          # (E, Hkv, D)
    wo: jax.Array          # (H, D, E)


class Layer(NamedTuple):
    mixer_norm: jax.Array
    mixer: Union[Mamba, Attention]
    mlp_norm: jax.Array
    gate: jax.Array        # (E, F)
    up: jax.Array          # (E, F)
    down: jax.Array        # (F, E)


class Weights(NamedTuple):
    embed: jax.Array       # (V, E): the head too
    layers: List[Layer]
    final_norm: jax.Array  # (E,)


class Arch(NamedTuple):
    """What the equations need of the configuration file."""

    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    rms_norm_eps: float


QUERY_BLOCK = 256
F32 = jnp.float32


def lower_precision(dtype):
    """`(array) -> array` that rounds to `dtype` and comes back to float32:
    the reference "computed in a lower precision" for calibrating the
    cell's tolerance (`benchmark/reference/granite4h_precision.py`), where
    every matrix product's two operands are rounded first. None is the
    reference itself."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def short_conv(x, taps, bias):
    """x (S, C), taps (K, C): y_t = silu(bias + sum_j taps[j] x_{t-(K-1)+j}),
    zeros before the sequence."""
    s, k = x.shape[0], taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    y = sum(taps[j].astype(F32) * xp[j:j + s] for j in range(k))
    return jax.nn.silu(y + bias.astype(F32))


def state_space(x, b_in, c_out, dt, a):
    """The recurrence, a token at a time. x (S, H, P), b_in, c_out (S, N),
    dt, a (S, H) -> y (S, H, P); the state (H, P, N) float32 starts at
    zero."""

    def step(st, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        st = (st * jnp.exp(a_t)[:, None, None]
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return st, jnp.einsum("hpn,n->hp", st, c_t)

    zero = jnp.zeros((x.shape[1], x.shape[2], b_in.shape[1]), F32)
    return jax.lax.scan(step, zero, (x, b_in, c_out, dt, a))[1]


def mamba_mixer(u, w: Mamba, a: Arch, r=lower_precision(None)):
    """u (S, E) float32, already normed -> (S, E)."""
    s = u.shape[0]
    H, P, N = a.mamba_heads, a.mamba_head_dim, a.mamba_state
    zxd = r(u) @ r(w.w_in.astype(F32))
    z, xbc, dt = jnp.split(zxd, [H * P, 2 * H * P + 2 * N], axis=-1)
    xbc = short_conv(xbc, w.conv, w.conv_bias)
    x, b_in, c_out = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    x = x.reshape(s, H, P)
    dt = jax.nn.softplus(dt + w.dt_bias.astype(F32))
    log_decay = -jnp.exp(w.a_log.astype(F32)) * dt
    y = state_space(x, b_in, c_out, dt, log_decay)
    y = y + w.d_skip.astype(F32)[None, :, None] * x
    y = _rms_norm(y.reshape(s, H * P) * jax.nn.silu(z), w.norm,
                  a.rms_norm_eps)
    return r(y) @ r(w.w_out.astype(F32))


def _attention(u, w: Attention, a: Arch, r):
    s = u.shape[0]
    u = r(u)
    q = jnp.einsum("se,ehd->shd", u, r(w.wq.astype(F32)))
    k = jnp.einsum("se,ehd->shd", u, r(w.wk.astype(F32)))
    v = jnp.einsum("se,ehd->shd", u, r(w.wv.astype(F32)))
    rep = q.shape[1] // k.shape[1]
    k = r(jnp.repeat(k, rep, axis=1))
    v = r(jnp.repeat(v, rep, axis=1))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(args):
        start, qb = args
        sc = jnp.einsum("shd,thd->hst", r(qb), k) * a.attention_multiplier
        seen = (start + jnp.arange(block))[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", r(p), v)

    o = jax.lax.map(rows, (jnp.arange(0, s, block),
                           q.reshape((s // block, block) + q.shape[1:])))
    return jnp.einsum("shd,hde->se", r(o.reshape(q.shape)),
                      r(w.wo.astype(F32)))


def layer(x, lyr: Layer, a: Arch, r=lower_precision(None)):
    """One block on x (S, E) float32."""
    u = _rms_norm(x, lyr.mixer_norm, a.rms_norm_eps)
    if isinstance(lyr.mixer, Mamba):
        mixed = mamba_mixer(u, lyr.mixer, a, r)
    else:
        mixed = _attention(u, lyr.mixer, a, r)
    x = x + a.residual_multiplier * mixed
    u = r(_rms_norm(x, lyr.mlp_norm, a.rms_norm_eps))
    m = r(jax.nn.silu(u @ r(lyr.gate.astype(F32)))
          * (u @ r(lyr.up.astype(F32)))) @ r(lyr.down.astype(F32))
    return x + a.residual_multiplier * m


def logits(w: Weights, ids, *, arch: Arch, operand_dtype=None):
    """ids: (S,) int32 -> (S, V) float32 logits of one sequence. With
    `operand_dtype` every matrix product's operands are first rounded to
    it (`lower_precision`; the recurrence and its state stay float32, as
    the program's do): NOT the reference, a yardstick for its tolerance."""
    r = lower_precision(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = arch.embedding_multiplier * w.embed[ids].astype(F32)
        for lyr in w.layers:
            x = layer(x, lyr, arch, r)
        x = _rms_norm(x, w.final_norm, arch.rms_norm_eps)
        return jnp.einsum("se,ve->sv", r(x), r(w.embed.astype(F32))
                          ) / arch.logits_scaling
