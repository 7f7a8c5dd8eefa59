"""The lowerings of Mamba2Attrs, a Mamba-2 state-space mixer whose memory
of the past is a fixed-size state (the second STATE op, beside
ops/kda_attention.py's delta-rule layer).

DENSE form (forward, training, the test oracle): the whole sequence from a
zero state, the recurrence a `lax.scan` over tokens.

PAGED form (the serving launch): the launch's B items of W rows are pieces
of requests; item i continues slot `state_slots[i]`'s state from row
`pos[i]` for `q_lens[i]` rows, by the SAME `item_chain` and
`conv_history` a KDA layer uses (ops/slot_state.py): a run's first item
reads the slot's stored state, or starts from zero where it is a request's
row 0 (zeroed ON THE DEVICE); rows past `q_lens` and items without rows
change nothing. The state is two leaves a node, indexed by SLOT: "s"
(slots, H, P, N) float32 and "conv" (slots, taps - 1, H P + 2 N), the
convolution's last input rows.

The recurrence runs in `ops/pallas/ssd_scan.py` on the TPU (or interpreted
on request) and as a scan over items and rows elsewhere, which is the
kernel's oracle. Two named scopes tell a device trace the parts apart:
`ssd_proj` around the two projections, the convolution and the gated norm,
`ssd_scan` around the recurrence.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.ops.pallas import ssd_scan
from flexflow_tpu.ops.slot_state import conv_history, item_chain, store

F32 = jnp.float32
PROJ_SCOPE = "ssd_proj"
SCAN_SCOPE = "ssd_scan"


def project(attrs, x, params):
    """x (B, S, E) -> (z (B, S, H P) float32, the gate; pre (B, S, H P +
    2 N) in x's dtype: xBC before the convolution, what the conv state
    holds; dt (B, S, H) float32 step sizes; a (B, S, H) log-decays)."""
    inner, c = attrs.inner, attrs.conv_dim
    zxd = jnp.dot(x, params["w_in"].astype(x.dtype),
                  preferred_element_type=F32)
    z, pre, dt = jnp.split(zxd, [inner, inner + c], axis=-1)
    dt = jax.nn.softplus(dt + params["dt_bias"].astype(F32))
    a = -jnp.exp(params["a_log"].astype(F32)) * dt
    return z, pre.astype(x.dtype), dt, a


def conv_xbc(attrs, pre, hist, params):
    """pre (B, S, C), hist (B, taps - 1, C): the rows before -> x (B, S,
    H, P), B, C (B, S, N) float32 after the convolution, its bias and
    SiLU. Tap taps - 1 multiplies the token itself."""
    B, S, _ = pre.shape
    taps = params["conv"].astype(F32)
    x = jnp.concatenate([hist, pre], axis=1).astype(F32)
    y = sum(taps[j] * x[:, j:j + S] for j in range(attrs.conv_taps))
    y = jax.nn.silu(y + params["conv_bias"].astype(F32))
    xh, b_in, c_out = jnp.split(
        y, [attrs.inner, attrs.inner + attrs.state_dim], axis=-1)
    return (xh.reshape(B, S, attrs.num_heads, attrs.head_dim), b_in, c_out)


def row_step(s, xh, b_in, c_out, dt, a):
    """One token: s (..., H, P, N), xh (..., H, P), b_in / c_out (..., N),
    dt / a (..., H) -> (new s, y (..., H, P) = S_t C_t)."""
    s = (s * jnp.exp(a)[..., None, None]
         + (dt[..., None] * xh)[..., None] * b_in[..., None, None, :])
    return s, jnp.einsum("...hpn,...n->...hp", s, c_out)


def finish(attrs, y, xh, z, params, x):
    """(B, S, H, P) read-outs -> (B, S, E): the skip, the gate BEFORE the
    norm, one RMSNorm over all H P channels, W_out."""
    B, S = y.shape[:2]
    y = y + params["d_skip"].astype(F32)[:, None] * xh
    y = y.reshape(B, S, -1) * jax.nn.silu(z)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + attrs.norm_eps)
    y = y * params["norm"].astype(F32)
    return jnp.dot(y.astype(x.dtype), params["w_out"].astype(x.dtype),
                   preferred_element_type=F32).astype(x.dtype)


def dense_mixer(attrs, x, params):
    """Every sequence of the batch from a zero state."""
    B = x.shape[0]
    with jax.named_scope(PROJ_SCOPE):
        z, pre, dt, a = project(attrs, x, params)
        hist = jnp.zeros((B, attrs.conv_taps - 1, pre.shape[-1]), pre.dtype)
        xh, b_in, c_out = conv_xbc(attrs, pre, hist, params)
    with jax.named_scope(SCAN_SCOPE):
        over_time = [jnp.moveaxis(t, 1, 0) for t in (xh, b_in, c_out, dt, a)]
        zero = jnp.zeros((B, attrs.num_heads, attrs.head_dim,
                          attrs.state_dim), F32)
        _, y = lax.scan(lambda s, r: row_step(s, *r), zero, over_time)
    with jax.named_scope(PROJ_SCOPE):
        return finish(attrs, jnp.moveaxis(y, 0, 1), xh, z, params, x)


def scan_items(xh, b_in, c_out, dt, a, chain, state):
    """The recurrence over a launch's items WITHOUT the kernel (its
    oracle): items in order, rows in order, the state carried along a
    run. Dead rows arrive with a = 0 and dt = 0."""
    slot, start, fresh, last = chain

    def item(s, xs):
        xi, bi, ci, di, ai, st, fr, sl = xs
        s = jnp.where(fr, 0.0, jnp.where(st, state[sl], s))
        s, y = lax.scan(lambda c, r: row_step(c, *r), s,
                        (xi, bi, ci, di, ai))
        return s, (y, s)

    _, (y, after) = lax.scan(item, jnp.zeros_like(state[0]),
                             (xh, b_in, c_out, dt, a, start, fresh, slot))
    return y, store(state, slot, last, after)


def paged_mixer(attrs, x, params, ctx):
    """The serving launch: returns (y, {"s": ..., "conv": ...})."""
    B, W, _ = x.shape
    H, P, N = attrs.num_heads, attrs.head_dim, attrs.state_dim
    q_lens = ctx.ragged_q_lens
    slots = ctx.state_slots
    if slots is None:       # the canonical launch: item i is slot i
        slots = jnp.arange(B, dtype=jnp.int32) % ctx.kv_cache["s"].shape[0]
    chain = item_chain(slots, jnp.asarray(ctx.cache_position), q_lens)
    with jax.named_scope(PROJ_SCOPE):
        z, pre, dt, a = project(attrs, x, params)
        hist, conv = conv_history(attrs, pre, q_lens, chain,
                                  ctx.kv_cache["conv"])
        xh, b_in, c_out = conv_xbc(attrs, pre, hist, params)
        alive = (jnp.arange(W, dtype=jnp.int32)[None, :]
                 < q_lens[:, None])[:, :, None]                  # (B, W, 1)
        dt = jnp.where(alive, dt, 0.0)
        a = jnp.where(alive, a, 0.0)
    interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    with jax.named_scope(SCAN_SCOPE):
        if ssd_scan.available(P, N, H, interp):
            slot, start, fresh, _last = chain
            y, state = ssd_scan.ssd_ragged_scan(
                (dt[..., None] * xh).reshape(B, W, H * P), b_in, c_out, a,
                ctx.kv_cache["s"], slot, start.astype(jnp.int32),
                fresh.astype(jnp.int32), q_lens.astype(jnp.int32), heads=H,
                interpret=interp)
            y = y.reshape(B, W, H, P)
        else:
            y, state = scan_items(xh, b_in, c_out, dt, a, chain,
                                  ctx.kv_cache["s"])
    with jax.named_scope(PROJ_SCOPE):
        out = finish(attrs, y, xh, z, params, x)
    return out, {"s": state, "conv": conv}
