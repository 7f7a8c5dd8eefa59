"""GLM-5.3-Flash's language model (`glm5_next_text`), written down plainly:
float32 `jax.numpy` under `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching. It imports nothing from the program.

The published `config.json` comes without modelling code, so the
equations are those of the papers its keys name, as ISSUE 48 sets them
out: manifold-constrained hyper-connections (arXiv:2512.24880) for the
residual path, Kimi Linear / KDA (arXiv:2510.26692) for the linear layers,
DeepSeek-V3.2-Exp's sparse attention with MoBA's pooled keys
(arXiv:2502.13189) for the others, DeepSeek-V3's router. On one sequence
of S tokens:

* THE RESIDUAL is X (S, n, C), n = `hc_mult` streams; X_0 is the embedding
  repeated n times. Around EVERY block F (an attention or an MLP, each
  with its own pre-norm) with its own phi (n C, n + n + n n), b, alpha (3):
      x~ = vec(X) / rms(vec(X))                 (no learned scale)
      Hpre = sigmoid(alpha_0 x~ phi_pre + b_pre),
      Hpost = 2 sigmoid(alpha_1 x~ phi_post + b_post)               (n each)
      Hres = M_iters, M_0 = exp(alpha_2 mat(x~ phi_res) + b_res),
             M_{i+1} = colnorm(rownorm(M_i))  (divide by the sum + hc_eps)
      X' = Hres X + Hpost^T F(RMSNorm(Hpre X))
  After the last layer the streams are summed; final RMSNorm; the head.
* KDA on h, H heads of d = 128: as benchmark/reference/ling3.py states it
  (convolution, SiLU, l2norm, the bounded gate, the delta rule as a plain
  `lax.scan`, the gated norm a head), with the decay's and the output
  gate's projections through rank `head_dim`: f = (h W_fa) W_fb,
  g = (h W_ga) W_gb.
* The SPARSE latent layer on h, position t:
      c_q = RMSNorm(h W_dq), q_j = c_q W_uq,j (no rope), c_kv = RMSNorm(h
      W_dkv), [k_j | v_j] = c_kv W_ukv,j                        (naive form)
      qI_i = c_q W_iq,i, kI = LayerNorm(h W_ik), w = h W_w Hi^-1/2 di^-1/2,
      rope on pairs (2i, 2i+1) of the first `index_rope_dim` values of both
      kP_b = mean of kI over tokens [pool b, pool (b+1)), whole blocks only
      I_{t,b} = sum_i w_{t,i} ReLU(qI_{t,i} . kP_b),  b < t // pool
      B_t = {t // pool} + the index_topk / pool - 1 blocks of largest I
            (`lax.top_k`: a tie to the lower b; all where fewer)
      softmax over {s <= t : s // pool in B_t} of q_j . k_{j,s} dqk^-1/2
* MLPs: SwiGLU with the clamp, silu(min(g, L)) * clip(u, -L, L); dense in
  the leading layers, else sigmoid scores over ALL routed experts, the top
  k of score + bias, weights the unbiased scores normalised, times
  `routed_scaling_factor`; the chosen experts HELD here plus the shared one.

What keeps a 33 k-row sequence inside one chip's memory beside 9.4 GB of
weights changes no sum: the weights arrive as the program stores them and
are upcast a layer, an expert at a time; whatever is a function of one
token runs over blocks of ROWS rows; a KDA layer is a scan over those
blocks that carries the state and the convolution's last rows; the sparse
layer expands K and V for HEAD_GROUP heads at a time; X' is written into
X's own buffer.
"""

from __future__ import annotations

from typing import List, NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Hc(NamedTuple):
    phi: jax.Array       # (n C, 2 n + n n)
    b: jax.Array         # (2 n + n n,)
    alpha: jax.Array     # (3,)


class Kda(NamedTuple):
    wq: jax.Array        # (E, H d)
    wk: jax.Array
    wv: jax.Array
    conv_q: jax.Array    # (K, H d): tap K - 1 multiplies the token itself
    conv_k: jax.Array
    conv_v: jax.Array
    w_fa: jax.Array      # (E, r)
    w_fb: jax.Array      # (r, H d)
    dt_bias: jax.Array   # (H d,)
    a_log: jax.Array     # (H,)
    w_beta: jax.Array    # (E, H)
    w_ga: jax.Array
    w_gb: jax.Array
    o_norm: jax.Array    # (d,)
    wo: jax.Array        # (H d, E)


class Dsa(NamedTuple):
    w_dq: jax.Array      # (E, q_lora_rank)
    q_norm: jax.Array
    w_uq: jax.Array      # (q_lora_rank, H, dqk)
    w_dkv: jax.Array     # (E, kv_lora_rank)
    kv_norm: jax.Array
    w_ukv: jax.Array     # (kv_lora_rank, H, dqk + dv)
    wo: jax.Array        # (H, dv, E)
    w_iq: jax.Array      # (q_lora_rank, Hi, di)
    w_ik: jax.Array      # (E, di)
    ik_scale: jax.Array  # (di,)
    ik_bias: jax.Array
    w_iw: jax.Array      # (E, Hi)


class Dense(NamedTuple):
    gate: jax.Array      # (E, F)
    up: jax.Array
    down: jax.Array      # (F, E)


class Moe(NamedTuple):
    router: jax.Array    # (E, n_routed_experts published)
    bias: jax.Array      # selection only
    w_gate: jax.Array    # (held, E, F)
    w_up: jax.Array
    w_down: jax.Array    # (held, F, E)
    shared_gate: jax.Array
    shared_up: jax.Array
    shared_down: jax.Array


class Layer(NamedTuple):
    attn_hc: Hc
    attn_norm: jax.Array
    attn: Union[Kda, Dsa]
    mlp_hc: Hc
    mlp_norm: jax.Array
    mlp: Union[Dense, Moe]


class Weights(NamedTuple):
    embed: jax.Array       # (V, E)
    layers: List[Layer]
    final_norm: jax.Array
    head: jax.Array        # (E, V)


class Arch(NamedTuple):
    """What the equations need of the configuration file."""

    kda_heads: int
    kda_head_dim: int
    kda_lower_bound: float
    heads: int
    qk_head_dim: int
    index_topk: int
    index_pool: int
    index_rope_dim: int
    index_rope_theta: float
    hc_streams: int
    hc_sinkhorn_iters: int
    hc_eps: float
    swiglu_limit: float
    experts_per_tok: int
    held_lo: int
    held_hi: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    # the three controls of tests/test_glm5.py, each a convention the
    # program must share to agree with this file: all False here
    drop_tail_block: bool = False
    pool_before_rope: bool = False
    skip_sinkhorn_round: bool = False


ROWS = 256
HEAD_GROUP = 8
L2_EPS = 1e-6
F32 = jnp.float32


def lower_precision(dtype):
    """`(array) -> array` that rounds to `dtype` and comes back to
    float32 (benchmark/reference/ling3.py says what for); None is the
    reference itself."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def _block(s: int) -> int:
    return ROWS if s % ROWS == 0 else s


def _by_rows(fn, *xs):
    """`fn` over blocks of rows of arrays whose first axis is the
    sequence: a function of one token has no other way to differ."""
    s = xs[0].shape[0]
    b = _block(s)
    out = lax.map(lambda t: fn(*t),
                  tuple(x.reshape((s // b, b) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape((s,) + o.shape[2:]), out)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + eps) * scale.astype(F32)


def _l2_norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ---------------------------------------------------------------------------
# the residual streams


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mixing(x, w: Hc, a: Arch):
    """X (S, n, C) -> (Hpre (S, n), Hpost (S, n), Hres (S, n, n))."""
    s, n, _ = x.shape
    flat = x.reshape(s, -1)
    # x~ phi = (vec X phi) / rms: the normed row is never stored
    z = (flat @ w.phi.astype(F32)) * lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + a.rms_norm_eps)
    alpha, b = w.alpha.astype(F32), w.b.astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(alpha[2] * z[:, 2 * n:] + b[2 * n:]).reshape(s, n, n)
    iters = a.hc_sinkhorn_iters - int(a.skip_sinkhorn_round)
    return pre, post, sinkhorn(res, iters, a.hc_eps)


def mix_out(x, y, post, res, r):
    """X' = Hres X + Hpost^T y, a block of rows at a time INTO X."""
    s = x.shape[0]
    b = _block(s)

    def rows(i, x):
        at = i * b
        cut = lambda t: lax.dynamic_slice_in_dim(t, at, b)  # noqa: E731
        new = (jnp.einsum("sij,sjc->sic", cut(res), cut(x))
               + cut(post)[:, :, None] * cut(y)[:, None, :])
        return lax.dynamic_update_slice_in_dim(x, r(new), at, 0)

    return lax.fori_loop(0, s // b, rows, x)


# ---------------------------------------------------------------------------
# KDA


def delta_rule(state, q, k, v, a, beta):
    """The recurrence, a token at a time, from `state` (H, d, d). q, k, a
    (S, H, d), v (S, H, d), beta (S, H) -> (state after, o (S, H, d))."""

    def step(st, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        st = st * jnp.exp(a_t)[:, :, None]
        u = jnp.einsum("hk,hkv->hv", k_t, st)
        st = st + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - u))
        return st, jnp.einsum("hk,hkv->hv", q_t, st)

    return lax.scan(step, state, (q, k, v, a, beta))


def _kda(h, w: Kda, a: Arch, r):
    s, H, d = h.shape[0], a.kda_heads, a.kda_head_dim
    b = _block(s)
    taps = jnp.concatenate([w.conv_q, w.conv_k, w.conv_v], axis=-1).astype(
        F32)                                                  # (K, 3 H d)
    keep = taps.shape[0] - 1
    up = lambda m: r(m.astype(F32))                         # noqa: E731

    def rows(carry, hb):
        state, tail = carry
        hb = r(hb)
        pre = jnp.concatenate([hb @ up(w.wq), hb @ up(w.wk), hb @ up(w.wv)],
                              axis=-1)                        # (b, 3 H d)
        seen = jnp.concatenate([tail, pre])
        y = jax.nn.silu(sum(taps[j] * seen[j:j + b]
                            for j in range(keep + 1)))
        q, k, v = jnp.split(y.reshape(b, 3 * H, d), 3, axis=1)
        q, k = _l2_norm(q) * d ** -0.5, _l2_norm(k)
        f = (r(hb @ up(w.w_fa)) @ up(w.w_fb)
             + w.dt_bias.astype(F32)).reshape(b, H, d)
        decay = a.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(w.a_log.astype(F32))[None, :, None] * f)
        beta = jax.nn.sigmoid(hb @ up(w.w_beta))
        state, o = delta_rule(state, q, k, v, decay, beta)
        gate = jax.nn.sigmoid(r(hb @ up(w.w_ga)) @ up(w.w_gb))
        o = _rms_norm(o, w.o_norm, a.rms_norm_eps) * gate.reshape(o.shape)
        return (state, seen[b:]), r(o.reshape(b, -1)) @ up(w.wo)

    zero = (jnp.zeros((H, d, d), F32), jnp.zeros((keep, 3 * H * d), F32))
    _, y = lax.scan(rows, zero, h.reshape(s // b, b, -1))
    return y.reshape(s, -1)


# ---------------------------------------------------------------------------
# the sparse latent layer


def _rope_pairs(x, theta: float):
    """x (S, ..., d): position s turns pair (2i, 2i + 1) by s theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _roped(x, a: Arch):
    n = a.index_rope_dim
    if not n:
        return x
    return jnp.concatenate([_rope_pairs(x[..., :n], a.index_rope_theta),
                            x[..., n:]], axis=-1)


def selection(h, c_q, w: Dsa, a: Arch, r):
    """(S, S // pool) bool: the whole blocks before its own that each
    query keeps."""
    s, p = h.shape[0], a.index_pool
    hi, di = w.w_iq.shape[1], w.w_iq.shape[2]
    q_i = _roped((c_q @ r(w.w_iq.astype(F32)).reshape(c_q.shape[1], -1)
                  ).reshape(s, hi, di), a)
    k = h @ r(w.w_ik.astype(F32))
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + a.rms_norm_eps)
    k = k * w.ik_scale.astype(F32) + w.ik_bias.astype(F32)
    nb = s // p
    if a.pool_before_rope:       # a control: NOT the convention taken
        # a pooled key roped as its block's first token
        pooled = jnp.mean(k[:nb * p].reshape(nb, p, di), axis=1)
        n = a.index_rope_dim
        turned = _rope_pairs(jnp.repeat(pooled[:, :n], p, axis=0),
                             a.index_rope_theta)[::p]
        pooled = jnp.concatenate([turned, pooled[:, n:]], axis=-1)
    else:
        pooled = jnp.mean(_roped(k, a)[:nb * p].reshape(nb, p, di), axis=1)
    pooled = r(pooled)
    weight = (h @ r(w.w_iw.astype(F32))) * (hi * di) ** -0.5
    blocks = a.index_topk // p - 1
    kept = min(blocks, nb)

    def rows(start, qb, wb):
        dots = jnp.einsum("shd,nd->shn", r(qb), pooled)
        score = jnp.sum(wb[:, :, None] * jax.nn.relu(dots), axis=1) + 0.0
        own = (start + jnp.arange(qb.shape[0])) // p
        valid = jnp.arange(nb)[None, :] < own[:, None]
        top, at = lax.top_k(jnp.where(valid, score, -jnp.inf), kept)
        return jnp.zeros(valid.shape, bool).at[
            jnp.arange(qb.shape[0])[:, None], at].set(top > -jnp.inf)

    if nb == 0:
        return jnp.zeros((s, 0), bool)
    b = _block(s)
    out = lax.map(lambda t: rows(*t),
                  (jnp.arange(0, s, b),
                   q_i.reshape((s // b, b) + q_i.shape[1:]),
                   weight.reshape(s // b, b, -1)))
    return out.reshape(s, -1)


def _dsa(h, w: Dsa, a: Arch, r):
    s, p, H = h.shape[0], a.index_pool, a.heads
    n = a.qk_head_dim
    h = r(h)
    c_q = r(_rms_norm(h @ r(w.w_dq.astype(F32)), w.q_norm, a.rms_norm_eps))
    c_kv = r(_rms_norm(h @ r(w.w_dkv.astype(F32)), w.kv_norm,
                       a.rms_norm_eps))
    chosen = selection(h, c_q, w, a, r)                       # (S, S // p)
    tok_block = jnp.arange(s) // p
    b = _block(s)
    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    scale = n ** -0.5

    def heads(acc, first):
        w_uq = lax.dynamic_slice_in_dim(w.w_uq, first, g, axis=1)
        w_ukv = lax.dynamic_slice_in_dim(w.w_ukv, first, g, axis=1)
        w_o = lax.dynamic_slice_in_dim(w.wo, first, g, axis=0)
        q = jnp.einsum("sc,chd->shd", c_q, r(w_uq.astype(F32)))
        kv = jnp.einsum("sc,chd->shd", c_kv, r(w_ukv.astype(F32)))
        k, v = r(kv[..., :n]), r(kv[..., n:])

        def rows(args):
            start, qb, cb = args
            at = start + jnp.arange(b)
            cols = jnp.arange(s)
            own = tok_block[None, :] == (at // p)[:, None]
            if a.drop_tail_block:    # a control: only the token itself
                own = cols[None, :] == at[:, None]
            # a token's verdict is its block's; the last, partial block
            # is only ever a query's own
            kept = jnp.pad(jnp.repeat(cb, p, axis=1),
                           ((0, 0), (0, s - cb.shape[1] * p)))
            seen = (at[:, None] >= cols[None, :]) & (kept | own)
            sc = jnp.einsum("shd,thd->hst", r(qb), k) * scale
            pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thd->shd", r(pr), v)

        o = lax.map(rows, (jnp.arange(0, s, b),
                           q.reshape((s // b, b) + q.shape[1:]),
                           chosen.reshape(s // b, b, -1)))
        o = o.reshape(s, g, -1)
        return acc + jnp.einsum("shd,hde->se", r(o), r(w_o.astype(F32))), None

    out, _ = lax.scan(heads, jnp.zeros_like(h), jnp.arange(0, H, g))
    return out


# ---------------------------------------------------------------------------
# the MLPs


def _swiglu(h, gate, up, down, limit, r=lower_precision(None)):
    g, u = h @ r(gate.astype(F32)), h @ r(up.astype(F32))
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return r(jax.nn.silu(g) * u) @ r(down.astype(F32))


def route(h, w: Moe, a: Arch):
    """(ids (S, k) over all routed experts, weights (S, k))."""
    s = jax.nn.sigmoid(h @ w.router.astype(F32))
    _, ids = lax.top_k(s + w.bias.astype(F32), a.experts_per_tok)
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
    per = jnp.sum(jnp.where(ids[:, :, None] == held[None, None, :],
                            wt[:, :, None], 0.0), axis=1)     # (S, held)

    def one(g, acc):
        return acc + per[:, g, None] * _swiglu(
            h, w.w_gate[g], w.w_up[g], w.w_down[g], a.swiglu_limit, r)

    routed = lax.fori_loop(0, a.held_hi - a.held_lo, one, jnp.zeros_like(h))
    return routed + _swiglu(h, w.shared_gate, w.shared_up, w.shared_down,
                            a.swiglu_limit, r)


# ---------------------------------------------------------------------------


def _around(x, hc: Hc, norm, fn, a: Arch, r):
    """X -> Hres X + Hpost^T fn(RMSNorm(Hpre X))."""
    pre, post, res = mixing(x, hc, a)
    h = _by_rows(lambda xb, pb: _rms_norm(
        jnp.einsum("sn,snc->sc", pb, xb), norm, a.rms_norm_eps), x, pre)
    return mix_out(x, fn(r(h)), post, res, r)


def layer(x, lyr: Layer, a: Arch, r=lower_precision(None), routes=None):
    """One layer on X (S, n, C) float32."""
    attn = _kda if isinstance(lyr.attn, Kda) else _dsa
    x = _around(x, lyr.attn_hc, lyr.attn_norm,
                lambda h: attn(h, lyr.attn, a, r), a, r)
    if isinstance(lyr.mlp, Dense):
        mlp = lambda h: _by_rows(lambda hb: _swiglu(   # noqa: E731
            hb, lyr.mlp.gate, lyr.mlp.up, lyr.mlp.down, a.swiglu_limit, r), h)
    else:
        mlp = lambda h: _experts(h, lyr.mlp, a, r, routes)  # noqa: E731
    return _around(x, lyr.mlp_hc, lyr.mlp_norm, mlp, a, r)


def logits(w: Weights, ids, *, arch: Arch, operand_dtype=None, routes=None):
    """ids: (S,) int32 -> (S, V) float32 logits of one sequence. With
    `operand_dtype` every matrix product's operands and the streams as
    stored are first rounded to it (`lower_precision`; the router's
    product, the mixing's coefficients and the recurrence stay float32,
    as the program's do): NOT the reference, a yardstick for its
    tolerance. `routes`, a list, collects each expert layer's (S, k)
    chosen experts."""
    r = lower_precision(operand_dtype)
    with jax.default_matmul_precision("highest"):
        e = w.embed[ids].astype(F32)
        x = jnp.repeat(e[:, None, :], arch.hc_streams, axis=1)
        for lyr in w.layers:
            x = layer(x, lyr, arch, r, routes)
        x = _rms_norm(jnp.sum(x, axis=1), w.final_norm, arch.rms_norm_eps)
        return r(x) @ r(w.head.astype(F32))
