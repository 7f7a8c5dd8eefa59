"""Paged LATENT attention: the ragged walk of paged/attention.py over a
pool that holds ONE row a token, `[c_kv | k_r]` (multi-head latent
attention, ops/attrs.py LatentAttentionAttrs).

The same descriptor drives it (`pos`, `q_lens`, `depths`, `anc`: decode,
chunked prefill and tree verify are one call), the same page table, the
same walk: grid (batch,), table / positions / query lengths scalar
prefetched, the pool left in HBM, an entry's LIVE pages copied in blocks
into a double-buffered VMEM block with the next block (or the next
entry's first) in flight. What differs is what a row is.

In the absorbed form a head's query is `[q_nope_h W_uk,h^T | q_rope_h]`
and every head scores against the SAME cache row: latent attention is
multi-QUERY attention over one "kv head" whose key is the whole row and
whose value is the row's first `kv_lora_rank` lanes. So

  * the pool has one entry a node, `(num_pages, page_size, lanes)` with
    lanes = latent_width rounded up to the 128-lane tile (320 -> 384: a
    row that is not a lane multiple cannot be windowed by Mosaic, and
    HBM tiles it to 384 whether or not the shape says so; the pad lanes
    are zero and score nothing);
  * a block is read ONCE and serves both matmuls: the scores contract
    all lanes, the values are lanes [0, value_lanes) of the same VMEM
    block (value_lanes = kv_lora_rank rounded up to 128, sliced back by
    the caller);
  * all q heads fold into the row dim (row = window row x heads + head),
    so a block's scores are ONE (rows, lanes) x (lanes, keys) matmul and
    there is no head loop;
  * per-head K and V never exist: 2 x lanes bytes a token and layer in
    bfloat16 against 2 x heads x (qk + v) uncompressed.

The softmax scale and the position scale on q are folded into q by the
caller (ops/latent_attention.py), so the kernel's scale is 1. The device
operation is named `mla_paged_attention`: metrics that read the GQA
kernel by name keep reading only that one.

A SPARSE latent layer (ops/latent_attention.py: an indexer keeps, a
query row, some blocks of `block_tokens` tokens) hands both forms
`block_keep` (B, S, blocks of the table) bool. THE MASKED WALK: the
kernel still walks every live page of the entry and adds the row's mask
to the scores, block of keys by block of keys; exact, and at a context
of c tokens it does c / index_topk times the matrix work a walk of the
selected blocks alone would (PERF.md section 7 keeps the gathering
kernel). The mask rides into VMEM a grid step as (key blocks, window
rows, keys) 0/1 values and is spread over the heads' folded rows by a
one-hot matmul, as the window's visibility is.

`latent_gather_attention` is the pure-JAX fallback and the CPU oracle,
as `ragged_gather_attention` is for the GQA kernel.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.paged.attention import (
    _KV_VMEM_SHARE,
    _SCORE_TILE_BYTES,
    ATTEND,
    KV_WRITE,
    LANES,
    NEG_INF,
    _reject,
    _round_up,
    _vmem_capacity_bytes,
    ragged_visibility_mask,
)

KERNEL_NAME = "mla_paged_attention"


def pool_lanes(latent_width: int) -> int:
    """Lanes of a latent pool row: the row's values, to the lane tile."""
    return _round_up(latent_width, LANES)


def latent_attention_available(page_size: int, interpret: bool = False,
                               dtype=jnp.float32) -> bool:
    """True when the latent Pallas kernel runs these shapes on this
    backend (the one gate for decode, chunk and tree windows; the row is
    lane-padded by construction, so only the page has to tile the
    sublane dim at the pool's dtype). FF_TPU_NO_PAGED=1 disables it like
    the GQA kernel. Rejections log once per (reason, config)."""
    dt = jnp.dtype(dtype)
    cfg = ("latent", page_size, dt.name, jax.default_backend())
    if os.environ.get("FF_TPU_NO_PAGED") == "1":
        return _reject("FF_TPU_NO_PAGED=1 kill switch set", cfg)
    if interpret:
        return True
    if dt.itemsize not in (2, 4):
        return _reject(f"latent pool dtype {dt.name} is not 2 or 4 bytes",
                       cfg)
    sublane = 8 * (4 // dt.itemsize)
    if page_size % sublane != 0:
        return _reject(
            f"page_size={page_size} does not tile the {sublane}-row "
            f"sublane dim at pool dtype {dt.name}", cfg)
    if jax.default_backend() != "tpu":
        return _reject(f"backend is {jax.default_backend()!r}, not tpu",
                       cfg)
    return True


def latent_block_pages(page_size: int, table_width: int, lanes: int,
                       pool_dtype, q_rows: int) -> int:
    """Pages a grid step's block holds, derived as `ragged_block_pages`
    derives the GQA kernel's: the double-buffered block (ONE buffer
    pair, the row is key and value) within its VMEM share, the
    (q_rows, keys) float32 score tile within _SCORE_TILE_BYTES, whole
    128-key tiles, no longer than the table. `q_rows` = heads x window."""
    page_bytes = page_size * lanes * jnp.dtype(pool_dtype).itemsize
    by_vmem = (_vmem_capacity_bytes() // _KV_VMEM_SHARE) // (2 * page_bytes)
    by_score = _SCORE_TILE_BYTES // (4 * max(q_rows, 1) * page_size)
    tile = max(1, LANES // page_size)
    ppb = max(1, min(by_vmem, by_score))
    if ppb >= tile:
        ppb -= ppb % tile
    return max(1, min(ppb, _round_up(table_width, tile)))


# ---------------------------------------------------------------------------
# pure-JAX fallback and oracle


def _token_keep(block_keep, block_tokens: int, length: int):
    """(B, S, blocks) -> (B, S, length) bool: a block's verdict on each
    of its tokens."""
    keep = jnp.repeat(block_keep, block_tokens, axis=-1)
    return jnp.pad(keep, ((0, 0), (0, 0), (0, length - keep.shape[-1])))


def latent_gather_attention(q, pool, page_tables, pos, q_lens, anc_mask, *,
                            value_lanes: int, block_keep=None,
                            block_tokens: int = 1):
    """q: (B, S, H, lanes) absorbed, scaled queries; pool: (N, P, lanes).
    Gathers every table-mapped page and runs dense masked attention of
    all heads against the one row a token: scores over all lanes, values
    the first `value_lanes`. Returns (B, S, H, value_lanes). Rows with
    no visible key come out as an average of garbage, which the caller's
    q_len bookkeeping discards (the kernel writes zeros there)."""
    B = q.shape[0]
    P = pool.shape[1]
    # float32 throughout: this is the oracle, and XLA's CPU backend has no
    # batched bfloat16 product with a float32 result
    rows = pool[page_tables].reshape(B, -1, pool.shape[2]).astype(
        jnp.float32)
    mask = ragged_visibility_mask(page_tables, pos, q_lens, anc_mask, P)
    if block_keep is not None:
        mask = mask & _token_keep(block_keep, block_tokens, mask.shape[-1])
    s = jnp.einsum("bshc,blc->bhsl", q.astype(jnp.float32), rows)
    p = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bhsl,blc->bshc", p, rows[..., :value_lanes])
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# the kernel


def _latent_kernel(pt_ref, pos_ref, qlen_ref, q_ref, c_hbm, anc_ref, *rest,
                   page_size, ppb, heads, value_lanes):
    """One batch entry a grid step: walk the entry's live pages in blocks
    of `ppb`, each page ONE contiguous (P, lanes) copy into the double-
    buffered block; scores of all folded rows against the block in one
    matmul, values the block's first `value_lanes` lanes. The walk, its
    prefetch across entries and the window's in-kernel visibility are
    paged/attention.py `_ragged_kernel`'s. A sparse layer's launch has one
    input more, `keep_ref` (key blocks, window rows, keys), which masks
    block j's scores besides."""
    *keep, o_ref, cbuf, sems, par_ref, bias_scr, m_scr, l_scr, acc_scr = rest
    keep_ref = keep[0] if keep else None
    b = pl.program_id(0)
    n_entries = pl.num_programs(0)
    n_table = pt_ref.shape[1]
    rows, window = anc_ref.shape
    keys = ppb * page_size

    def live_pages(e):
        horizon = pos_ref[e] + qlen_ref[e]
        n = jnp.minimum((horizon + page_size - 1) // page_size, n_table)
        return jnp.where(qlen_ref[e] > 0, n, 0)

    def block_copies(e, j, buf, fn):
        first = j * ppb
        n = jnp.clip(live_pages(e) - first, 0, ppb)

        def one(i, _):
            page = pt_ref[e, first + i]
            dst = pl.ds(pl.multiple_of(i * page_size, page_size),
                        page_size)
            fn(pltpu.make_async_copy(c_hbm.at[page], cbuf.at[buf, dst],
                                     sems.at[buf]))
            return 0

        lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _():
        # a block's tail past the live pages is never copied and its
        # scores are masked by ADDING: stale VMEM must not read as NaN
        cbuf[...] = jnp.zeros_like(cbuf)
        par_ref[0] = 0
        block_copies(0, 0, 0, lambda c: c.start())

    pos = pos_ref[b]
    qlen = qlen_ref[b]
    n_blocks = (live_pages(b) + ppb - 1) // ppb
    par = par_ref[0]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    bias_scr[...] = jnp.zeros_like(bias_scr)

    def start_next(j, buf):
        within = j + 1 < n_blocks
        e = jnp.minimum(jnp.where(within, b, b + 1), n_entries - 1)

        @pl.when(within | (b + 1 < n_entries))
        def _():
            block_copies(e, jnp.where(within, j + 1, 0), buf,
                         lambda c: c.start())

    @pl.when(n_blocks == 0)
    def _():
        start_next(-1, par)

    def block(j, _):
        buf = (par + j) % 2
        start_next(j, 1 - buf)
        block_copies(b, j, buf, lambda c: c.wait())
        first_key = j * keys

        @pl.when(first_key + keys > pos)
        def _():
            # the window's visibility from `anc` by a one-hot matmul
            # against the block's relative positions (attention.py)
            wrow = lax.broadcasted_iota(jnp.int32, (window, keys), 0)
            rel = first_key - pos + lax.broadcasted_iota(
                jnp.int32, (window, keys), 1)
            onehot = ((rel == wrow) & (wrow < qlen)).astype(anc_ref.dtype)
            vis = lax.dot_general(
                anc_ref[...], onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5
            col = first_key + lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1)
            bias_scr[...] = jnp.where((col < pos) | vis, 0.0, NEG_INF)

        cdt = q_ref.dtype
        c = cbuf[buf].astype(cdt)                           # (keys, lanes)
        s = lax.dot_general(q_ref[...], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s + bias_scr[...]
        if keep_ref is not None:
            # window row w's verdicts to its heads' folded rows
            # [w heads, (w + 1) heads)
            kw = keep_ref.shape[1]
            frow = lax.broadcasted_iota(jnp.int32, (rows, kw), 0)
            wrow = lax.broadcasted_iota(jnp.int32, (rows, kw), 1) * heads
            spread = ((frow >= wrow) & (frow < wrow + heads)).astype(
                keep_ref.dtype)
            kept = lax.dot_general(
                spread, keep_ref[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5
            s = jnp.where(kept, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(cdt), c[:, :value_lanes],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return 0

    lax.fori_loop(0, n_blocks, block, 0)
    par_ref[0] = (par + n_blocks) % 2

    # always written: a padded entry gives zeros, rows at or past q_len
    # are zeroed though they accumulated the prefix (folded row i is
    # window row i // heads)
    live = lax.broadcasted_iota(jnp.int32, acc_scr.shape, 0) < qlen * heads
    l_safe = jnp.maximum(l_scr[:, 0:1], 1e-30)
    o_ref[...] = jnp.where(live, acc_scr[...] / l_safe,
                           0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_lanes", "interpret",
                                             "block_tokens"))
def latent_flash_attention(q, pool, page_tables, pos, q_lens, anc_mask, *,
                           value_lanes: int, interpret: bool = False,
                           block_keep=None, block_tokens: int = 1):
    """The latent Pallas launch. q: (B, S, H, lanes) absorbed and scaled;
    pool: (N, P, lanes); page_tables (B, max_pages); pos, q_lens (B,);
    anc_mask (B, S, S) bool. Returns (B, S, H, value_lanes); rows at or
    past q_lens[b] are zeros. Jitted, so a model's layers trace and
    lower one kernel a launch shape."""
    B, S, H, lanes = q.shape
    P = pool.shape[1]
    n_pages = page_tables.shape[1]
    rows = _round_up(H * S, 8 * (4 // q.dtype.itemsize))
    window = _round_up(S, LANES)
    ppb = latent_block_pages(P, n_pages, lanes, pool.dtype, H * S)
    keys = ppb * P
    qr = jnp.pad(q.reshape(B, S * H, lanes),
                 ((0, 0), (0, rows - S * H), (0, 0)))
    anc_f = jnp.pad(
        jnp.repeat(anc_mask, H, axis=1).astype(jnp.bfloat16),
        ((0, 0), (0, rows - S * H), (0, window - S)))
    imap = lambda b, pt, ps, ql: (b, 0, 0)                  # noqa: E731
    extra_in, extra_specs = (), []
    if block_keep is not None:
        # the rows' verdicts a TOKEN, cut into the walk's blocks of keys:
        # (B, key blocks, window rows to the sublane tile, keys) 0/1
        n_kb = -(-n_pages // ppb)
        kw = _round_up(S, 16)
        tok = _token_keep(block_keep, block_tokens, n_kb * keys)
        tok = jnp.pad(tok, ((0, 0), (0, kw - S), (0, 0))).astype(
            jnp.bfloat16)
        extra_in = (tok.reshape(B, kw, n_kb, keys).transpose(0, 2, 1, 3),)
        extra_specs = [pl.BlockSpec((None, n_kb, kw, keys),
                                    lambda b, pt, ps, ql: (b, 0, 0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, rows, lanes), imap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, rows, window), imap),
        ] + extra_specs,
        out_specs=pl.BlockSpec((None, rows, value_lanes), imap),
        scratch_shapes=[
            pltpu.VMEM((2, keys, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, keys), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, value_lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=P, ppb=ppb, heads=H,
                          value_lanes=value_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, value_lanes), q.dtype),
        # buffer parity and an in-flight copy carry from entry to entry
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q_lens.astype(jnp.int32), qr, pool, anc_f, *extra_in)
    return out[:, :S * H].reshape(B, S, H, value_lanes)


# ---------------------------------------------------------------------------
# the one lowering entry: page write + attend


def latent_paged_attention(q, row, pool, page_tables, pos, q_lens,
                           anc_mask, *, value_width: int, block_keep=None,
                           block_tokens: int = 1):
    """One paged latent-attention step. q: (B, S, H, latent_width)
    absorbed queries with every scale folded in; row: (B, S,
    latent_width) the tokens' `[c_kv | k_r]` (k_r roped); pool: (N, P,
    lanes). Scatters the live rows into their table-mapped pages (rows
    past q_len or past the table land in the null page), then attends by
    the kernel or the gather fallback behind the one gate. Returns
    ((B, S, H, value_width) latent outputs, new pool). `block_keep`
    (B, S, table blocks of `block_tokens` tokens) bool, a sparse layer's:
    a row attends to the visible tokens of the blocks it keeps."""
    B, S, H, width = q.shape
    P, lanes = pool.shape[1], pool.shape[2]
    pos_v, qlen_v = jnp.asarray(pos), jnp.asarray(q_lens)
    with jax.named_scope(KV_WRITE):
        L = page_tables.shape[1] * P
        rows = pos_v[:, None] + jnp.arange(S)[None, :]
        safe = jnp.minimum(rows, L - 1)
        page = page_tables[jnp.arange(B)[:, None], safe // P]
        live = (rows < L) & (jnp.arange(S)[None, :] < qlen_v[:, None])
        page = jnp.where(live, page, 0)
        pad = ((0, 0),) * (row.ndim - 1) + ((0, lanes - width),)
        pool = pool.at[page, safe % P].set(
            jnp.pad(row, pad).astype(pool.dtype))
    with jax.named_scope(ATTEND):
        qp = jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - width),))
        value_lanes = min(lanes, _round_up(value_width, LANES))
        interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
        if latent_attention_available(P, interpret=interp,
                                      dtype=pool.dtype):
            out = latent_flash_attention(
                qp, pool, page_tables, pos_v, qlen_v, anc_mask,
                value_lanes=value_lanes, interpret=interp,
                block_keep=block_keep, block_tokens=block_tokens)
        else:
            out = latent_gather_attention(
                qp, pool, page_tables, pos_v, qlen_v, anc_mask,
                value_lanes=value_lanes, block_keep=block_keep,
                block_tokens=block_tokens)
        return out[..., :value_width], pool
