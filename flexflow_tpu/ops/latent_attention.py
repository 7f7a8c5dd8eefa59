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

SPARSE layer (`index_heads` > 0, ops/attrs.py has the equations): an
indexer scores every query row against one POOLED key a block of
`index_pool` tokens, `select_blocks` keeps the `index_blocks` best whole
blocks before the row's own (exactly: a tie goes to the lower block), and
either form attends under that mask. The paged form keeps the pooled keys
on the pages of the latent rows (entry "kp" of the node's pool dict); the
block a slot is still filling is accumulated in place and never scored,
because the row's own block is always kept. Three named scopes tell the
parts apart in a device trace: `dsa_index` (the pooled keys' write, their
gather and the scores), `dsa_select`, `dsa_attend`.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.paged.attention import ATTEND, KV_WRITE, OUT, QKV


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
    return _rotate(x, positions, yarn_inv_freq(attrs), attrs.rope_interleave)


def _rotate(x, positions, inv_freq, interleave):
    d = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq)                                           # (B, S, d/2)
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


def query_latent(attrs, x, params):
    """c_q = RMSNorm(x W_dq), what the heads' queries AND the indexer's
    are projected from; x itself without the low-rank step."""
    if attrs.q_lora_rank is None:
        return x
    return _rms(_dot(x, params["w_dq"]), params["q_norm"], attrs.norm_eps)


def project(attrs, x, params, positions, c_q=None):
    """x: (B, S, E) -> (q_nope (B,S,H,n), q_rope (B,S,H,r) roped and
    scaled, c_kv (B,S,c) normed, k_r (B,S,r) roped); q_nope carries the
    query scale too. Without a rope part (`qk_rope_head_dim` 0) q_rope
    and k_r are None: no zero-width array is built."""
    B, S, _ = x.shape
    H, n = attrs.num_heads, attrs.qk_nope_head_dim
    if c_q is None:
        c_q = query_latent(attrs, x, params)
    q = _dot(c_q, params["w_uq"].reshape(c_q.shape[-1], -1))
    q = q.reshape(B, S, H, attrs.qk_head_dim)
    qs = query_scale(attrs, positions)[:, :, None, None]
    q_nope = (q[..., :n].astype(jnp.float32) * qs).astype(x.dtype)
    kv = _dot(x, params["w_dkv"])
    c_kv = _rms(kv[..., :attrs.kv_lora_rank], params["kv_norm"],
                attrs.norm_eps)
    if not attrs.qk_rope_head_dim:
        return q_nope, None, c_kv, None
    q_rope = (rope(q[..., n:], positions, attrs).astype(jnp.float32)
              * qs).astype(x.dtype)
    k_r = rope(kv[..., attrs.kv_lora_rank:], positions, attrs)
    return q_nope, q_rope, c_kv, k_r


# ---------------------------------------------------------------------------
# the indexer of a sparse layer


def index_project(attrs, x, c_q, params, positions):
    """(qI (B,S,Hi,di), kI (B,S,di), w (B,S,Hi) float32): the indexer's
    queries from c_q, its one key a token (LayerNorm with scale and bias)
    and the heads' weights, the first `index_rope_dim` values of qI and
    kI roped on interleaved pairs."""
    B, S, _ = x.shape
    Hi, di, r = attrs.index_heads, attrs.index_dim, attrs.index_rope_dim
    q = _dot(c_q, params["w_iq"].reshape(c_q.shape[-1], -1)).reshape(
        B, S, Hi, di)
    k = jnp.dot(x, params["w_ik"].astype(x.dtype),
                preferred_element_type=jnp.float32)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                          + attrs.norm_eps)
    k = (k * params["ik_scale"].astype(jnp.float32)
         + params["ik_bias"].astype(jnp.float32)).astype(x.dtype)
    if r:
        inv = attrs.index_rope_theta ** (
            -np.arange(0, r, 2, dtype=np.float64) / r)
        inv = inv.astype(np.float32)
        q = jnp.concatenate([_rotate(q[..., :r], positions, inv, True),
                             q[..., r:]], axis=-1)
        k = jnp.concatenate([_rotate(k[..., :r], positions, inv, True),
                             k[..., r:]], axis=-1)
    w = jnp.dot(x, params["w_iw"].astype(x.dtype),
                preferred_element_type=jnp.float32) * (Hi * di) ** -0.5
    return q, k, w


def index_scores(q_i, w, pooled):
    """I = sum_i w_i ReLU(qI_i . kP): q_i (B,S,Hi,di), w (B,S,Hi),
    pooled (B,NB,di) -> (B,S,NB) float32 (+0.0: no negative zero, whose
    place among equal scores would depend on who sorts)."""
    dots = jnp.einsum("bshd,bnd->bshn", q_i, pooled.astype(q_i.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2) + 0.0


def select_blocks(scores, valid, k: int):
    """(..., NB) bool: the `k` largest of the `valid` scores of each row
    (all of them where fewer are valid), a tie to the LOWER index: what a
    stable descending sort would keep, found without sorting. A score's
    bits, read as an integer whose order is the floats', are searched bit
    by bit for the k-th largest value T (32 rounds of one comparison and
    one count over the row); everything above T is kept, and of those
    equal to T the first k - (count above). Exact whatever k is: the TPU
    lowers `lax.top_k` to a sort of the whole axis, and k is 511 of
    thousands here, 576 rows a launch."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    # unsigned order; an invalid place sorts below every float
    u = jnp.where(valid,
                  jax.lax.bitcast_convert_type(key, jnp.uint32)
                  ^ jnp.uint32(0x80000000), jnp.uint32(0))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum((u >= cand).astype(jnp.int32), axis=-1,
                         keepdims=True) >= k
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, bit,
                          jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > t
    equal = u == t
    need = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    first = jnp.cumsum(equal.astype(jnp.int32), axis=-1) <= need
    return valid & (above | (equal & first))


def dense_block_mask(attrs, x, c_q, params, positions):
    """(B, S, S) bool, whole sequences from position 0: token s is among
    what query t attends to (causality apart)."""
    B, S, _ = x.shape
    p = attrs.index_pool
    with jax.named_scope("dsa_index"):
        q_i, k_i, w = index_project(attrs, x, c_q, params, positions)
        nb = S // p
        pooled = jnp.mean(k_i[:, :nb * p].astype(jnp.float32).reshape(
            B, nb, p, -1), axis=2).astype(x.dtype)
        scores = index_scores(q_i, w, pooled)
    own = positions // p                                     # (B, S)
    with jax.named_scope("dsa_select"):
        blocks = jnp.arange(nb)
        sel = select_blocks(scores, blocks[None, None, :] < own[..., None],
                            attrs.index_blocks)
    # a token's verdict is its block's; a partial last block is only
    # ever a query's own
    chosen = jnp.pad(jnp.repeat(sel, p, axis=-1),
                     ((0, 0), (0, 0), (0, S - nb * p)))
    return chosen | ((jnp.arange(S) // p)[None, None, :] == own[..., None])


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


def _attend_scope(attrs):
    """`dsa_attend` around a sparse layer's attention; a dense latent
    layer's program is left as it was, names included."""
    return (jax.named_scope("dsa_attend") if attrs.index_heads
            else contextlib.nullcontext())


def naive_attention(attrs, x, params):
    """Causal attention of the whole sequence, per-head K and V expanded
    from c_kv (the dense lowering); a sparse layer's selection is one
    more mask."""
    B, S, _ = x.shape
    n = attrs.qk_nope_head_dim
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    c_q = query_latent(attrs, x, params)
    q_nope, q_rope, c_kv, k_r = project(attrs, x, params, positions, c_q)
    kv = jnp.einsum("bsc,chd->bshd", c_kv, params["w_ukv"].astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    k_nope, v = kv[..., :n], kv[..., n:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    if q_rope is not None:
        s = s + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r,
                           preferred_element_type=jnp.float32)
    seen = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if attrs.index_heads:
        seen = seen & dense_block_mask(attrs, x, c_q, params,
                                       positions)[:, None]
    with _attend_scope(attrs):
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(x.dtype), v,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return output(attrs, o, params, x)


def absorbed_queries(attrs, q_nope, q_rope, params):
    """(B, S, H, latent_width): [q_nope_h W_uk,h^T | q_rope_h]."""
    w_uk = params["w_ukv"][..., :attrs.qk_nope_head_dim]     # (c, H, n)
    q_abs = jnp.einsum("bshn,chn->bshc", q_nope, w_uk.astype(q_nope.dtype),
                       preferred_element_type=jnp.float32).astype(
                           q_nope.dtype)
    if q_rope is None:
        return q_abs
    return jnp.concatenate([q_abs, q_rope], axis=-1)


def absorbed_output(attrs, o_lat, params, x):
    """(B, S, H, c) latent outputs -> (B, S, E): W_uv per head, then W_o."""
    w_uv = params["w_ukv"][..., attrs.qk_nope_head_dim:]     # (c, H, v)
    o = jnp.einsum("bshc,chv->bshv", o_lat, w_uv.astype(o_lat.dtype),
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return output(attrs, o, params, x)


DSA_STATS = ("selected_distinct",)


def paged_index_select(attrs, x, c_q, params, ctx, positions):
    """The indexer in a paged launch: the launch's rows' keys pooled into
    their pages' "kp" rows, every row scored against the pooled keys its
    table maps, the selection. Returns (keep (B, S, NB) bool over the
    table's blocks of `index_pool` tokens, the row's own block among
    them; the new "kp" pool; stats (len(DSA_STATS),) int32).

    The write. A block's pooled key is the mean of its tokens' keys, and
    a block may arrive in pieces: across launches (a chunk boundary, a
    decode step a token) or across two items of one launch. So the item
    sums what it brings to each block it touches in float32 (`local`
    blocks: an item of W rows touches at most ceil(W / pool) + 1), the
    launch first ZEROES the row of every block whose first token it
    carries (a page comes off the free list with a stranger's rows in
    it), then ADDS every item's part. A block that arrives whole in one
    item, as every block of an aligned chunk does, is written with one
    rounding."""
    B, S, _ = x.shape
    kp = ctx.kv_cache["kp"]
    P = ctx.kv_cache["c"].shape[1]
    p, R = attrs.index_pool, kp.shape[1]
    tables = ctx.page_tables
    n_table = tables.shape[1]
    NB = n_table * R
    pos = jnp.asarray(ctx.cache_position)
    q_lens = ctx.ragged_q_lens
    live = jnp.arange(S)[None, :] < q_lens[:, None]           # (B, S)
    with jax.named_scope("dsa_index"):
        q_i, k_i, w = index_project(attrs, x, c_q, params, positions)
        n_local = -(-S // p) + 1
        block = (pos // p)[:, None] + jnp.arange(n_local)[None, :]  # (B, l)
        member = live[:, None, :] & (
            (positions // p)[:, None, :] == block[:, :, None])     # (B, l, S)
        part = jnp.einsum("bls,bsd->bld", member.astype(jnp.float32),
                          k_i.astype(jnp.float32)) / p
        touched = jnp.any(member, axis=-1) & (block < NB)
        starts = touched & (block * p >= pos[:, None])
        safe = jnp.minimum(block, NB - 1)
        page = jnp.take_along_axis(tables, safe // R, axis=1)
        row = safe % R
        with jax.named_scope(KV_WRITE):
            kp = kp.at[jnp.where(starts, page, 0), row].set(0)
            kp = kp.at[jnp.where(touched, page, 0), row].add(
                jnp.where(touched[..., None], part, 0.0).astype(kp.dtype))
        pooled = kp[tables].reshape(B, NB, kp.shape[2])
        scores = index_scores(q_i, w, pooled)
    own = (positions // p)[..., None]                          # (B, S, 1)
    blocks = jnp.arange(NB)
    with jax.named_scope("dsa_select"):
        sel = select_blocks(scores, (blocks < own) & live[..., None],
                            attrs.index_blocks)
        keep = sel | ((blocks == own) & live[..., None])
        # latent rows the launch has to READ, each once a slot however
        # many of the slot's rows chose it: items of one slot share a
        # table row; the union is taken at the slot's first live item
        any_row = jnp.any(keep, axis=1)                          # (B, NB)
        alive = q_lens > 0
        same = jnp.all(tables[:, None, :] == tables[None, :, :], axis=-1) \
            & alive[:, None] & alive[None, :]
        union = jnp.einsum("ij,jn->in", same.astype(jnp.float32),
                           any_row.astype(jnp.float32)) > 0.5
        idx = jnp.arange(B)
        first = alive & ~jnp.any(same & (idx[None, :] < idx[:, None]),
                                 axis=1)
        horizon = jnp.max(jnp.where(same, (pos + q_lens)[None, :], 0),
                          axis=1)                                 # (B,)
        tokens = jnp.clip(horizon[:, None] - blocks * p, 0, p)   # (B, NB)
        distinct = jnp.sum(jnp.where(first[:, None] & union, tokens, 0))
    return keep, kp, jnp.stack([distinct]).astype(jnp.int32)


def paged_attention(attrs, x, params, ctx):
    """The paged step (decode, chunk, tree verify alike): rows at
    pos + depths, the tokens' latent rows appended to the pool, absorbed
    attention over the page table; a sparse layer's indexer first (rows
    in chain order: tree verify is refused on a latent graph). Returns
    (y, {pool entry: new pool}, a sparse layer's stats or None). The node
    names the four parts an attention node has on the paged path
    (paged/attention.py): `QKV` and `OUT` here, the indexer under
    `ATTEND` with its pooled keys' write `KV_WRITE` inside; the rows'
    write and the walk are named in the call (paged/latent.py)."""
    from flexflow_tpu.paged.latent import latent_paged_attention

    positions = jnp.asarray(ctx.cache_position)[:, None] + ctx.ragged_depths
    with jax.named_scope(QKV):
        c_q = query_latent(attrs, x, params)
        q_nope, q_rope, c_kv, k_r = project(attrs, x, params, positions,
                                            c_q)
        q = absorbed_queries(attrs, q_nope, q_rope, params)
        row = c_kv if k_r is None else jnp.concatenate([c_kv, k_r],
                                                       axis=-1)
    pools, keep, stats = {}, None, None
    if attrs.index_heads:
        with jax.named_scope(ATTEND):
            keep, pools["kp"], stats = paged_index_select(
                attrs, x, c_q, params, ctx, positions)
    with _attend_scope(attrs):
        o_lat, pools["c"] = latent_paged_attention(
            q, row, ctx.kv_cache["c"], ctx.page_tables, ctx.cache_position,
            ctx.ragged_q_lens, ctx.ragged_anc,
            value_width=attrs.kv_lora_rank, block_keep=keep,
            block_tokens=attrs.index_pool)
    with jax.named_scope(OUT):
        return absorbed_output(attrs, o_lat, params, x), pools, stats
