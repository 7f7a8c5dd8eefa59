"""The lowerings of KdaAttentionAttrs, a delta-rule linear-attention layer
whose memory of the past is a fixed-size state.

DENSE form (forward, training, the test oracle): the whole sequence from a
zero state, the recurrence a `lax.scan` over tokens.

PAGED form (the serving launch): the launch's B items of W rows are pieces
of requests; item i continues slot `state_slots[i]`'s state from row
`pos[i]` for `q_lens[i]` rows. What the lowering is promised: the items
of one slot are CONSECUTIVE and in row order. It derives the rest
(`ops/slot_state.py` `item_chain`, shared with ops/mamba2.py): a run's first item reads the slot's stored state, or
starts from zero where it is a request's row 0 (the state is zeroed ON
THE DEVICE, by the launch that carries row 0: a slot's reuse cannot see
its predecessor's state and admission uploads nothing); the following
items take the state the item before left; the run's last item stores it.
Rows past `q_lens` and items without rows change nothing. The state is
two leaves a node, indexed by SLOT: "s" (slots, H, d, d) float32 and
"conv" (slots, taps - 1, 3 H d), the convolution's last input rows.

The recurrence runs in `ops/pallas/kda_scan.py` on the TPU (or
interpreted on request) and as a scan over items and rows elsewhere,
which is the kernel's oracle.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.ops.pallas import kda_scan
from flexflow_tpu.ops.slot_state import conv_history, item_chain, store

L2_EPS = 1e-6
F32 = jnp.float32


def _dot32(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=F32)


def _gate_proj(x, params, name):
    """x W (full rank) or (x W_a) W_b (`gate_rank`), float32."""
    if name in params:
        return _dot32(x, params[name])
    return _dot32(_dot32(x, params[name + "a"]).astype(x.dtype),
                  params[name + "b"])


def project(attrs, x, params):
    """x (B, S, E) -> (pre (B, S, 3 H d) in x's dtype: q~ | k~ | v~ before
    the convolution, what the conv state holds; a (B, S, H, d) float32
    log-decay; beta (B, S, H); gate (B, S, H, d))."""
    B, S, _ = x.shape
    H, d = attrs.num_heads, attrs.head_dim
    pre = jnp.concatenate(
        [_dot32(x, params[n]) for n in ("wq", "wk", "wv")],
        axis=-1).astype(x.dtype)
    f = (_gate_proj(x, params, "w_f") + params["dt_bias"].astype(F32)
         ).reshape(B, S, H, d)
    a = attrs.lower_bound * jax.nn.sigmoid(
        jnp.exp(params["a_log"].astype(F32))[:, None] * f)
    beta = jax.nn.sigmoid(_dot32(x, params["w_beta"]))
    gate = jax.nn.sigmoid(_gate_proj(x, params, "w_g")).reshape(B, S, H, d)
    return pre, a, beta, gate


def conv_qkv(attrs, pre, hist, params):
    """pre (B, S, 3c), hist (B, taps - 1, 3c): the rows before -> q, k, v
    (B, S, H, d) float32 after the convolution, SiLU and the norms."""
    B, S, _ = pre.shape
    H, d = attrs.num_heads, attrs.head_dim
    taps = jnp.concatenate([params[n].astype(F32) for n in
                            ("conv_q", "conv_k", "conv_v")], axis=-1)
    x = jnp.concatenate([hist, pre], axis=1).astype(F32)
    y = sum(taps[j] * x[:, j:j + S] for j in range(attrs.conv_taps))
    q, k, v = jnp.split(jax.nn.silu(y).reshape(B, S, 3 * H, d), 3, axis=2)

    def l2(t):
        return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    return l2(q) * d ** -0.5, l2(k), v


def row_step(s, q, k, v, a, beta):
    """One token: s (..., H, d, d), q / k / v / a (..., H, d), beta
    (..., H) -> (new s, o (..., H, d))."""
    s = s * jnp.exp(a)[..., None]
    u = jnp.einsum("...hk,...hkv->...hv", k, s)
    s = s + jnp.einsum("...hk,...hv->...hkv", k, beta[..., None] * (v - u))
    return s, jnp.einsum("...hk,...hkv->...hv", q, s)


def finish(attrs, o, gate, params, x):
    """(B, S, H, d) read-outs -> (B, S, E): the norm a head, the gate, W_o."""
    B, S = o.shape[:2]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + attrs.norm_eps)
    o = o * params["o_norm"].astype(F32) * gate
    return jnp.dot(o.reshape(B, S, -1).astype(x.dtype),
                   params["wo"].astype(x.dtype),
                   preferred_element_type=F32).astype(x.dtype)


def dense_attention(attrs, x, params):
    """Every sequence of the batch from a zero state."""
    B, S, _ = x.shape
    H, d = attrs.num_heads, attrs.head_dim
    pre, a, beta, gate = project(attrs, x, params)
    hist = jnp.zeros((B, attrs.conv_taps - 1, pre.shape[-1]), pre.dtype)
    q, k, v = conv_qkv(attrs, pre, hist, params)

    def step(s, xs):
        return row_step(s, *xs)

    over_time = [jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta)]
    _, o = lax.scan(step, jnp.zeros((B, H, d, d), F32), over_time)
    return finish(attrs, jnp.moveaxis(o, 0, 1), gate, params, x)


def scan_items(q, k, v, a, beta, chain, state):
    """The recurrence over a launch's items WITHOUT the kernel (its
    oracle): items in order, rows in order, the state carried along a
    run. Dead rows arrive with a = 0 and beta = 0."""
    slot, start, fresh, last = chain

    def item(s, xs):
        qi, ki, vi, ai, bi, st, fr, sl = xs
        s = jnp.where(fr, 0.0, jnp.where(st, state[sl], s))
        s, o = lax.scan(lambda c, r: row_step(c, *r), s,
                        (qi, ki, vi, ai, bi))
        return s, (o, s)

    _, (o, after) = lax.scan(item, jnp.zeros_like(state[0]),
                             (q, k, v, a, beta, start, fresh, slot))
    return o, store(state, slot, last, after)


def paged_attention(attrs, x, params, ctx):
    """The serving launch: returns (y, {"s": ..., "conv": ...})."""
    B, W, _ = x.shape
    H, d = attrs.num_heads, attrs.head_dim
    q_lens = ctx.ragged_q_lens
    slots = ctx.state_slots
    if slots is None:       # the canonical launch: item i is slot i
        slots = jnp.arange(B, dtype=jnp.int32) % ctx.kv_cache["s"].shape[0]
    chain = item_chain(slots, jnp.asarray(ctx.cache_position), q_lens)
    pre, a, beta, gate = project(attrs, x, params)
    hist, conv = conv_history(attrs, pre, q_lens, chain,
                              ctx.kv_cache["conv"])
    q, k, v = conv_qkv(attrs, pre, hist, params)
    alive = (jnp.arange(W, dtype=jnp.int32)[None, :]
             < q_lens[:, None])                              # (B, W)
    a = jnp.where(alive[:, :, None, None], a, 0.0)
    beta = jnp.where(alive[:, :, None], beta, 0.0)
    interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    if kda_scan.available(d, interp):
        pad = (-W) % kda_scan.ROWS

        def flat(t):        # (B, W, H, d) -> (B, rows of the kernel, H d)
            return jnp.pad(t.reshape(B, W, H * d), ((0, 0), (0, pad),
                                                    (0, 0)))

        slot, start, fresh, _last = chain
        o, state = kda_scan.kda_ragged_scan(
            flat(q), flat(k), flat(k * beta[..., None]), flat(v), flat(a),
            ctx.kv_cache["s"], slot, start.astype(jnp.int32),
            fresh.astype(jnp.int32), q_lens.astype(jnp.int32), heads=H,
            interpret=interp)
        o = o[:, :W].reshape(B, W, H, d)
    else:
        o, state = scan_items(q, k, v, a, beta, chain, ctx.kv_cache["s"])
    return finish(attrs, o, gate, params, x), {"s": state, "conv": conv}
