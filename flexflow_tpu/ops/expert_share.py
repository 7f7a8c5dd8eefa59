"""The lowering of ExpertShareAttrs: route over all experts, compute the
held ones without dropping a token, add the shared expert.

On the TPU (or interpreted on request) the held experts run through the
grouped kernels of ops/pallas/grouped_experts.py; elsewhere through a
dense loop over the held experts, which is the kernels' oracle. Either
way a token's result is a function of that token alone.
"""

from __future__ import annotations

import logging
import os

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.ops.pallas import grouped_experts as ge

logger = logging.getLogger(__name__)
_logged = set()

STATS = ("moe_assignments", "experts_hit", "experts_held",
         "moe_rows_padded")


def route(attrs, x, router, bias=None):
    """x: (T, d) -> (ids (T, k) int32 over ALL experts, weights (T, k)
    float32). Logits, scores and the renormalisation in float32. The
    scoring function and the groups are the attrs': softmax over one
    group is the default; with `n_group` > 1 only the `topk_group`
    groups whose two largest scores sum highest stay open, and `bias`
    (`select_bias`) moves the SELECTION alone: the weights are the
    unbiased scores of the chosen."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = (jax.nn.sigmoid(logits) if attrs.score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    w, ids = _select(attrs, probs, bias)
    if attrs.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * attrs.routed_scale


def _top_k(x, k):
    """The k largest along the last axis and where they lie, (..., k)
    each, in descending order, a tie to the lower index: `lax.top_k`'s
    contract, by k rounds of (maximum, first index that holds it, strike
    it out). The TPU lowers `lax.top_k` to a sort of the whole axis with
    the indices riding along; k is a static and at most 8 here. The axis
    must hold k values above -inf: a struck position reads -inf."""
    n = x.shape[-1]
    if k > n:
        raise ValueError(f"the {k} largest of {n} values")
    at = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    place = lax.broadcasted_iota(jnp.int32, x.shape[:-1] + (k,),
                                 x.ndim - 1)

    def round_(r, state):
        taken, vals, ids = state
        left = jnp.where(taken, -jnp.inf, x)
        top = jnp.max(left, axis=-1, keepdims=True)
        first = jnp.min(jnp.where(left == top, at, n), axis=-1,
                        keepdims=True)
        return (taken | (at == first), jnp.where(place == r, top, vals),
                jnp.where(place == r, first, ids))

    # up to four rounds are written out; more run as a loop over ONE
    # round's code: 2.7 us slower a call at 576 x 512 on a v5e, and the
    # programs that hold six such layers compile and load a tenth
    # sooner (PERF.md section 6, PR 46). A loop around two or four
    # rounds of a short axis costs more than the rounds.
    _, vals, ids = lax.fori_loop(
        0, k, round_, (jnp.zeros(x.shape, bool),
                       jnp.zeros(place.shape, x.dtype),
                       jnp.zeros(place.shape, jnp.int32)),
        unroll=k <= 4)
    return vals, ids


def _select(attrs, scores, bias):
    """Top k of (T, E) scores by score + bias within the open groups;
    returns the UNBIASED scores of the chosen and their ids."""
    T, E = scores.shape
    open_outputs = attrs.topk_group * (E // attrs.n_group)
    if attrs.k > open_outputs:
        raise ValueError(
            f"{attrs.k} experts a token of the {open_outputs} outputs that "
            f"{attrs.topk_group} of {attrs.n_group} groups over {E} hold")
    if bias is None and attrs.n_group == 1:
        # one short sort stays: the rounds save nothing at 128 or 64
        # outputs (2.1 -> 6.0 us at 8 rows of 64 on a v5e) and a dozen
        # layers of them compile and load longer (PERF.md section 6)
        return lax.top_k(scores, attrs.k)
    chosen_by = scores if bias is None else scores + bias.astype(
        jnp.float32)
    if attrs.n_group > 1:
        groups = chosen_by.reshape(T, attrs.n_group, E // attrs.n_group)
        group_score = jnp.sum(_top_k(groups, 2)[0], axis=-1)
        _, best = _top_k(group_score, attrs.topk_group)
        is_open = jnp.any(best[:, :, None] == jnp.arange(attrs.n_group),
                          axis=1)
        chosen_by = jnp.where(is_open[:, :, None], groups,
                              -jnp.inf).reshape(T, E)
    _, ids = _top_k(chosen_by, attrs.k)
    # the chosen's scores by a masked maximum, so exact, and ONE (T, k)
    # array: `route` sums it over k, and a sum whose terms come out of k
    # separate rounds (or out of a masked sum, which a compiler merges
    # with it) is added in another order on the TPU and differs in the
    # last bit. A gather of (T, k) out of (T, E) is 45 us at 576 x 512
    # on a v5e; this is 3
    at = lax.broadcasted_iota(jnp.int32, (T, attrs.k, E), 2)
    return jnp.max(jnp.where(at == ids[:, :, None], scores[:, None, :],
                             -jnp.inf), axis=-1), ids


def _swiglu(x, gate, up, down, limit=0.0):
    dt = x.dtype
    g = jnp.dot(x, gate.astype(dt), preferred_element_type=jnp.float32)
    u = jnp.dot(x, up.astype(dt), preferred_element_type=jnp.float32)
    g, u = ge.clamp(g, u, limit)
    h = (g * jax.nn.sigmoid(g) * u).astype(dt)
    return jnp.dot(h, down.astype(dt), preferred_element_type=jnp.float32)


def kernels_available(d: int, f: int, interpret: bool) -> bool:
    if interpret:
        return True
    why = None
    if jax.default_backend() != "tpu":
        why = f"backend is {jax.default_backend()!r}, not tpu"
    elif d % ge.LANES or f % ge.LANES:
        why = f"widths {d} / {f} are not multiples of {ge.LANES} lanes"
    if why and why not in _logged:
        _logged.add(why)
        logger.log(logging.WARNING if jax.default_backend() == "tpu"
                   else logging.INFO,
                   "expert share: grouped kernels rejected (%s); using "
                   "the dense loop over held experts", why)
    return why is None


def routed(attrs, x, ids, w, params, live=None):
    """The held experts' part of the result, (T, d) float32, and the
    launch's counters (STATS order, int32). `live` (T,) bool, if given,
    names the rows that are tokens: a launch's pad rows (an idle slot of
    a decode tick, the tail of a piece) belong to no expert, so they are
    neither computed nor counted and stream no expert's weights."""
    T, d = x.shape
    lo, hi = attrs.held
    G, f = hi - lo, attrs.hidden_dim
    held = (ids >= lo) & (ids < hi)
    if live is not None:
        held = held & live[:, None]
    local = jnp.where(held, ids - lo, G).reshape(-1)          # (T * k,)
    interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    if kernels_available(d, f, interp):
        A = T * attrs.k
        tm = ge.row_tile(A, G, x.dtype)
        dest, tile_group, n_active, counts = ge.layout(local, G, tm)
        rows = ge.num_tiles(A, G, tm) * tm
        # the padded layout by a GATHER: row r's token, or T (a zero row)
        token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), attrs.k)
        src = jnp.full((rows,), T, jnp.int32).at[dest].set(
            token, mode="drop")
        xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
        h = ge.grouped_swiglu(xs, params["w_gate"].astype(x.dtype),
                              params["w_up"].astype(x.dtype), tile_group,
                              n_active, tm=tm, limit=attrs.swiglu_limit,
                              interpret=interp)
        y = ge.grouped_dot(h, params["w_down"].astype(x.dtype), tile_group,
                           n_active, tm=tm, out_dtype=jnp.float32,
                           interpret=interp)
        # rows of inactive tiles were never written: select, do not scale
        per = jnp.where(held.reshape(-1, 1),
                        jnp.take(y, jnp.minimum(dest, rows - 1), axis=0),
                        0.0)
        out = jnp.sum(per.reshape(T, attrs.k, d) * w[..., None], axis=1)
        padded = n_active[0] * tm - jnp.sum(counts)
    else:
        wt = jnp.sum(jnp.where(
            (local.reshape(T, attrs.k, 1) == jnp.arange(G)), w[..., None],
            0.0), axis=1)                                     # (T, G)
        out = jnp.zeros((T, d), jnp.float32)
        for g in range(G):
            out = out + wt[:, g:g + 1] * _swiglu(
                x, params["w_gate"][g], params["w_up"][g],
                params["w_down"][g], attrs.swiglu_limit)
        counts = jnp.sum(local[:, None] == jnp.arange(G), axis=0)
        padded = jnp.int32(0)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.int32(G), padded]).astype(jnp.int32)
    return out, stats


def expert_share(attrs, x, params, live=None):
    """x: (..., d) -> (y (..., d) in x's dtype, stats (4,) int32). `live`
    (...) bool: the rows that are tokens (`routed`); a pad row's result
    is its shared expert's alone and nobody reads it."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    ids, w = route(attrs, xt, params["router"], params.get("bias"))
    y, stats = routed(attrs, xt, ids, w, params,
                      None if live is None else live.reshape(-1))
    if attrs.shared_hidden:
        y = y + _swiglu(xt, params["shared_gate"], params["shared_up"],
                        params["shared_down"], attrs.swiglu_limit)
    return y.astype(x.dtype).reshape(shape), stats
