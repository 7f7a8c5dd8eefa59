"""Ragged paged attention: ONE kernel for decode, chunked prefill, and
speculative tree verify.

Every unit of paged work — a decode step, a chunk of a mid-prefill
prompt, a drafted token tree — is the same shape of problem: S query
rows per batch entry whose K/V rows land at cache rows pos..pos+S-1
through a page table, attending over the committed prefix plus some
subset of the in-flight window. The only thing that differs is the
per-slot metadata:

  * ``pos``    (B,)     absolute committed position (the write head);
  * ``q_lens`` (B,)     how many of the S query rows are real work
                        (decode 1, a chunk its token count, a tree its
                        node count; 0 marks a padded batch entry);
  * ``depths`` (B, S)   rope offset of row i relative to pos (chunks:
                        arange(S); trees: node depth, so sibling
                        branches score at the SAME absolute position);
  * ``anc``    (B, S, S) the visibility relation INSIDE the window
                        (chunks: lower-triangular causal; trees: the
                        ancestor-or-self mask; decode: ones((1, 1))).

One Pallas kernel consumes that descriptor: the grid walks
(batch, kv head, page) with the page table, positions and query lengths
SCALAR-PREFETCHED, so each page's K/V block DMAs straight from its
pooled HBM location into VMEM — no gathered copy of the sequence ever
materializes, and no (B, S, L) HBM mask is built either: the window
visibility is derived IN-KERNEL from `anc` via a one-hot matmul against
the page's relative positions. Pages wholly past a slot's visible
horizon (pos + q_len - 1) are skipped, as are padded batch entries
(q_len == 0). GQA groups the q heads of one kv head into a single
(rep, S, D) block, so kv pages are read once per GROUP and never
repeated.

Pool layout: FLAT-LANE pages, (num_pages, page_size, Hkv * D) — a cache
row is one token's K (or V) for every kv head side by side on the lane
dim, the same layout the flash kernels read projections in
(ops/pallas/flash_attention.py). The kernel's page block is
(page_size, D) and the kv head coordinate picks its 128-aligned lane
block, which is the only way Mosaic can window one head of a page: a
block that squeezes the head out of a second-minor (…, Hkv, D) dim is
refused, and reshaping a head-minor pool at the pallas_call boundary
is a physical relayout of the whole pool under TPU tiling, per layer
per step. The append is one Hkv*D-lane row per token.

The single pure-JAX fallback (`ragged_gather_attention`) gathers
``pool[page_table]`` and applies the same visibility as a materialized
(B, S, L) mask (`ragged_visibility_mask`) — it runs anywhere and is the
reference the kernel is validated against in tests/test_paged.py.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

logger = logging.getLogger(__name__)
_fallback_logged: set = set()


def reset_rejection_log() -> None:
    """Forget which kernel rejections were already logged. Server
    construction calls this so a SECOND server (or an A/B run flipping
    FF_TPU_NO_PAGED between runs in one process) logs its own gate
    decisions instead of inheriting the first server's silence."""
    _fallback_logged.clear()


def _reject(reason: str, cfg: tuple) -> bool:
    """Log the CONCRETE kernel-rejection reason once per
    (reason, gate-config) pair (the flash-attention selection
    discipline: a silent fallback looks like a 10x paged-decode
    slowdown with no explanation in any log). Keying on the gate config
    too means two servers with different shapes each get their own
    line. On a TPU backend the line is a WARNING — there the gather
    path is a degraded mode somebody has to see; elsewhere (CPU tests)
    it is the expected path and stays at INFO."""
    key = (reason, cfg)
    if key not in _fallback_logged:
        _fallback_logged.add(key)
        level = (logging.WARNING if jax.default_backend() == "tpu"
                 else logging.INFO)
        logger.log(
            level,
            "paged attention: ragged Pallas kernel rejected (%s) for "
            "gate config (head_dim, page_size, pool dtype, backend)=%s; "
            "using the jnp.take gather fallback", reason, cfg)
    return False


def paged_attention_available(head_dim: int, page_size: int,
                              interpret: bool = False,
                              dtype=jnp.float32) -> bool:
    """True when the ragged Pallas kernel supports these shapes on this
    backend — the ONE gate for decode, chunked prefill and tree verify
    (there is no per-variant rejection matrix any more).
    FF_TPU_NO_PAGED=1 disables the kernel everywhere (A/B runs and
    kernel-bug escape hatch, like FF_TPU_NO_FLASH). On real TPUs the
    head dim must be a lane multiple (the kernel windows one head's
    D-wide lane block out of a flat-lane page row; smaller head dims
    take the gather fallback, mirroring the flash bshd gate) and pages
    must tile the sublane dim AT THE POOL'S DTYPE — (8, 128) tiles for
    fp32 but (16, 128) for bf16/fp16 and (32, 128) for int8/fp8, so a
    bf16 pool needs page_size % 16 == 0 and a QUANTIZED int8 pool
    (kv_dtype="int8", paged/quant.py) needs page_size % 32 == 0. These
    are the shapes Mosaic compiled and the gather reference confirmed on
    a v5e (tools/chip_kernels.py): decode, chunk and tree windows, GQA
    with 8 kv heads, every pool dtype at one-tile and 128-row pages.
    Rejections log their concrete reason once per (reason, config)."""
    dt = jnp.dtype(dtype)
    cfg = (head_dim, page_size, dt.name, jax.default_backend())
    if os.environ.get("FF_TPU_NO_PAGED") == "1":
        return _reject("FF_TPU_NO_PAGED=1 kill switch set", cfg)
    if interpret:
        return True
    itemsize = dt.itemsize
    if itemsize > 4:
        return _reject(
            f"pool dtype {dt.name} is 8-byte (no TPU tiling story)", cfg)
    sublane = 8 * (4 // max(itemsize, 1))
    if head_dim % LANES != 0:
        return _reject(
            f"head_dim={head_dim} is not a multiple of the {LANES}-lane "
            "tile", cfg)
    if page_size % sublane != 0:
        return _reject(
            f"page_size={page_size} does not tile the {sublane}-row "
            f"sublane dim at pool dtype {dt.name}", cfg)
    if jax.default_backend() != "tpu":
        return _reject(f"backend is {jax.default_backend()!r}, not tpu",
                       cfg)
    return True


# ---------------------------------------------------------------------------
# visibility reference + pure-JAX fallback


def ragged_visibility_mask(page_tables, pos, q_lens, anc_mask,
                           page_size: int):
    """(B, S, L) bool visibility, L = max_pages x P: the REFERENCE
    semantics both paths implement. Cache row kpos is visible to query
    row t of slot b when it is committed (kpos < pos[b]) or lies in the
    slot's in-flight window (rel = kpos - pos[b] in [0, q_lens[b])) on
    t's visibility path (anc_mask[b, t, rel]). Everything else — padded
    window rows past q_len, stale rows from earlier wider launches, the
    null page — stays masked. Chunks pass a lower-triangular anc_mask
    (causal within the chunk); trees pass the ancestor-or-self
    relation; decode is the S=1 special case of either."""
    B, S, _ = anc_mask.shape
    L = page_tables.shape[1] * page_size
    kpos = jnp.arange(L)
    rel = jnp.broadcast_to(kpos[None, None, :] - pos[:, None, None],
                           (B, S, L))
    in_window = (rel >= 0) & (rel < q_lens[:, None, None])
    anc = jnp.take_along_axis(anc_mask, jnp.clip(rel, 0, S - 1), axis=2)
    return (kpos[None, None, :] < pos[:, None, None]) | (in_window & anc)


def tree_visibility_mask(page_tables, pos, anc_mask, page_size: int):
    """Tree-verify visibility (the pre-ragged name, kept as the test /
    fallback reference): all S window rows are live, so this is
    ragged_visibility_mask with q_lens = S."""
    B, S, _ = anc_mask.shape
    full = jnp.full((B,), S, jnp.int32)
    return ragged_visibility_mask(page_tables, pos, full, anc_mask,
                                  page_size)


def ragged_gather_attention(q, kc_pages, vc_pages, page_tables, pos,
                            q_lens, anc_mask, *, scale: float,
                            k_scales=None, v_scales=None):
    """Pure-JAX fallback AND numerical reference for the ragged kernel:
    gather every table-mapped page (`pool[page_table]`) and run dense
    masked dot-product attention under ragged_visibility_mask. q:
    (B, S, H, D); kc/vc_pages: (N, P, Hkv*D); page_tables:
    (B, max_pages) int32; pos/q_lens: (B,) int32; anc_mask: (B, S, S)
    bool. For a quantized pool, k_scales/v_scales are the (N, Hkv)
    per-page sidecar (paged/quant.py) and the gathered int8 pages are
    dequantized by the SAME table gather before the dense attention.
    Rows with no visible keys (padded entries) come out of the
    all-masked softmax as a uniform average — garbage a caller's
    q_len bookkeeping already discards, exactly like the kernel's
    zero rows."""
    B, S, _, D = q.shape
    Hkv = kc_pages.shape[2] // D
    P = kc_pages.shape[1]
    dt = q.dtype
    if k_scales is not None:
        from flexflow_tpu.paged.quant import dequantize_pages

        kg = dequantize_pages(kc_pages[page_tables],
                              k_scales[page_tables])
        vg = dequantize_pages(vc_pages[page_tables],
                              v_scales[page_tables])
    else:
        kg = kc_pages[page_tables]
        vg = vc_pages[page_tables]
    kg = kg.reshape(B, -1, Hkv, D)
    vg = vg.reshape(B, -1, Hkv, D)
    mask = ragged_visibility_mask(page_tables, pos, q_lens, anc_mask, P)
    from flexflow_tpu.ops.jax_ops import _dot_product_attention

    if k_scales is not None:
        # match the Pallas kernel's quantized discipline: compute the
        # whole attention in f32 (dequantized pages stay f32, q is
        # upcast) and cast only the output back — downcasting the
        # dequantized gather to a bf16 q dtype would re-quantize it
        out = _dot_product_attention(q.astype(jnp.float32), kg, vg,
                                     causal=False, scale=scale,
                                     mask=mask)
        return out.astype(dt)
    return _dot_product_attention(q, kg.astype(dt), vg.astype(dt),
                                  causal=False, scale=scale, mask=mask)


# ---------------------------------------------------------------------------
# the ragged Pallas kernel: grid (B, Hkv, page); page table, positions and
# query lengths prefetched; window visibility derived in-kernel


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _ragged_kernel(pt_ref, pos_ref, qlen_ref, q_ref, k_ref, v_ref, *rest,
                   scale, page_size, n_pages, rep, quantized):
    """One (batch entry, kv head, page) grid step. Every tile is 2-D —
    (rows, D) q per head of the group, (P, D) K/V page, (rows, P)
    scores — the only shapes Mosaic's matmul takes; the q heads of the
    group are a static loop over the leading block dim."""
    if quantized:
        ks_ref, vs_ref, anc_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        anc_ref, o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(2)
    rows, window = anc_ref.shape

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    qlen = qlen_ref[b]
    # pages wholly past the slot's visible horizon (committed prefix +
    # its own q_len window rows) contribute nothing, and padded batch
    # entries (q_len == 0) do no work at all — skip the MXU work
    # entirely (the masked-out math would be exp(-inf) = 0)
    @pl.when((j * page_size <= pos + qlen - 1) & (qlen > 0))
    def _():
        k = k_ref[...]                       # (P, D)
        v = v_ref[...]
        # window visibility without a gather and without an HBM mask:
        # column c holds cache row j*P + c, i.e. window index
        # rel[c] = j*P + c - pos. One-hot it against the window rows
        # (zeroing indices past q_len) and contract with the anc
        # relation: (anc @ onehot)[t, c] = anc[t, rel[c]] when
        # 0 <= rel[c] < q_len, else 0. The contraction dim is the
        # window padded to a lane multiple, so the matmul is aligned.
        wrow = lax.broadcasted_iota(jnp.int32, (window, page_size), 0)
        rel = j * page_size - pos + lax.broadcasted_iota(
            jnp.int32, (window, page_size), 1)
        onehot = ((rel == wrow) & (wrow < qlen)).astype(jnp.float32)
        tree_vis = lax.dot_general(
            anc_ref[...], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5   # (rows, P)
        col = j * page_size + lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        vis = (col < pos) | tree_vis
        if quantized:
            # quantized pool: the page's per-head scale rode in as a
            # (1, P) row addressed by the same (b, j) as the page, so
            # dequant-on-load is a row-broadcast multiply on the SCORES
            # and the PROBABILITIES — the int8 page is what DMA'd from
            # HBM and what the MXU contracts; fp K/V never exist
            cdt = jnp.float32
        else:
            # a mixed-precision pool (e.g. bf16 kv_dtype under an fp32
            # model) computes at q's dtype: dot_general needs matching
            # operand dtypes
            cdt = q_ref.dtype
        k = k.astype(cdt)
        v = v.astype(cdt)
        for r in range(rep):  # fflint: host-ok (static unroll in the kernel trace)
            q = q_ref[r].astype(cdt)         # (rows, D)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks_ref[...]
            s = jnp.where(vis, s, NEG_INF)
            m_prev = m_scr[r, :, 0:1]
            l_prev = l_scr[r, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_ref[...]
            pc = p.astype(cdt)  # fflint: dtype-ok (this head's p, in VMEM)
            pv = lax.dot_general(pc, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            acc_scr[r] = acc_scr[r] * corr + pv
            m_scr[r] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[r] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    # finalize UNCONDITIONALLY: a padded entry whose every page was
    # skipped must still write (zeros), not leave o_ref as garbage —
    # and rows at or past q_len are forced to zero even when they
    # accumulated prefix attention (they share the entry's pages, so
    # the compute loop cannot skip them row-wise)
    @pl.when(j == n_pages - 1)
    def _():
        live = lax.broadcasted_iota(jnp.int32, acc_scr.shape[1:], 0) < qlen
        for r in range(rep):  # fflint: host-ok (static unroll in the kernel trace)
            l_safe = jnp.maximum(l_scr[r, :, 0:1], 1e-30)
            o_ref[r] = jnp.where(live, acc_scr[r] / l_safe,
                                 0.0).astype(o_ref.dtype)


def ragged_flash_attention(q, kc_pages, vc_pages, page_tables, pos,
                           q_lens, anc_mask, *, scale: float,
                           interpret: bool = False, k_scales=None,
                           v_scales=None):
    """The ragged Pallas launch. q: (B, S, H, D) — S is the launch's
    window width, per-entry real work is q_lens[b] <= S rows;
    kc/vc_pages: (N, P, Hkv*D) flat-lane pages (module docstring);
    page_tables: (B, max_pages); pos, q_lens: (B,); anc_mask: (B, S, S)
    bool window visibility. The page table, positions AND query lengths
    ride scalar prefetch, so each grid step's BlockSpec index map
    resolves `pt[b, j]` BEFORE the DMA and the horizon/padding skip
    predicates on prefetched scalars; the kv head coordinate picks the
    page's D-wide lane block. The anc relation is one VMEM block per
    batch entry — the only mask state, O(B*S^2) instead of a (B, S, L)
    HBM mask. q rows are padded to the sublane tile and the window to a
    lane multiple so every in-kernel matmul is tile-aligned. For a
    quantized pool, k_scales/v_scales are the (N, Hkv) sidecar: the
    table-mapped scales are gathered here (B * max_pages * Hkv floats)
    and each grid step reads its page's scale as a (1, P) row. Rows at
    or past q_lens[b] output zeros."""
    B, S, H, D = q.shape
    P = kc_pages.shape[1]
    Hkv = kc_pages.shape[2] // D
    rep = H // Hkv
    n_pages = page_tables.shape[1]
    rows = _round_up(S, 8 * (4 // q.dtype.itemsize))
    window = _round_up(S, LANES)
    qr = jnp.pad(q.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, rows - S), (0, 0)))   # (B, H, rows, D)
    anc_f = jnp.pad(anc_mask.astype(jnp.float32),
                    ((0, 0), (0, rows - S), (0, window - S)))
    quantized = k_scales is not None

    qmap = lambda b, g, j, pt, ps, ql: (b, g, 0, 0)         # noqa: E731
    kvmap = lambda b, g, j, pt, ps, ql: (pt[b, j], 0, g)    # noqa: E731
    in_specs = [
        pl.BlockSpec((None, rep, rows, D), qmap),
        pl.BlockSpec((None, P, D), kvmap),
        pl.BlockSpec((None, P, D), kvmap),
    ]
    operands = [qr, kc_pages, vc_pages]
    if quantized:
        smap = lambda b, g, j, pt, ps, ql: (b, g, j, 0, 0)  # noqa: E731
        for sc in (k_scales, v_scales):  # fflint: host-ok (trace-time, K then V)
            rows_sc = sc[page_tables].transpose(0, 2, 1)    # (B, Hkv, pages)
            operands.append(jnp.broadcast_to(
                rows_sc[..., None, None], (B, Hkv, n_pages, 1, P)))
            in_specs.append(pl.BlockSpec((None, None, None, 1, P), smap))
    in_specs.append(pl.BlockSpec((None, rows, window),
                                 lambda b, g, j, pt, ps, ql: (b, 0, 0)))
    operands.append(anc_f)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, rep, rows, D), qmap),
        scratch_shapes=[
            pltpu.VMEM((rep, rows, LANES), jnp.float32),
            pltpu.VMEM((rep, rows, LANES), jnp.float32),
            pltpu.VMEM((rep, rows, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, page_size=P,
                          n_pages=n_pages, rep=rep, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rows, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q_lens.astype(jnp.int32), *operands)
    return out[:, :, :S].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# the ONE lowering entry: rope + page write + attend, for every variant


def ragged_paged_attention(q, k, v, cache_k, cache_v, page_tables, pos,
                           q_lens, depths, anc_mask, *, scale: float,
                           rope_theta: Optional[float] = None,
                           k_scales=None, v_scales=None):
    """The single paged-attention step every caller lowers to — decode,
    chunked prefill and tree verify are the same call with different
    descriptors (module docstring). Ropes q/k at pos + depths, scatters
    the live K/V rows into their table-mapped pages (rows past q_len or
    past the table land in the null page with the other garbage — a
    padded row must clobber neither a real row nor the pool bounds),
    then attends via the ragged kernel or the gather fallback behind
    the one availability gate.

    When k_scales/v_scales are passed, the pools are int8 and the write
    becomes quantize-on-append under grow-only per-(page, head) scales
    (paged/quant.py): the roped fp rows never reach HBM, and BOTH
    attention paths dequantize on load.

    Returns (attention output, new k pool, new v pool) — plus
    (new k_scales, new v_scales) in the quantized case. Output rows at
    or past q_lens[b] are garbage by contract (kernel: zeros; gather:
    an unmasked-softmax average) — callers index by their own q_len
    bookkeeping."""
    from flexflow_tpu.ops.jax_ops import apply_rope
    from flexflow_tpu.paged.quant import quantized_append

    B, S = q.shape[0], q.shape[1]
    P = cache_k.shape[1]
    pos_v = jnp.asarray(pos)
    qlen_v = jnp.asarray(q_lens)
    if rope_theta is not None:
        positions = pos_v[:, None] + depths                # (B, S)
        q = apply_rope(q, rope_theta, pos_offset=positions)
        k = apply_rope(k, rope_theta, pos_offset=positions)
    L = page_tables.shape[1] * P
    rows = pos_v[:, None] + jnp.arange(S)[None, :]         # (B, S)
    safe = jnp.minimum(rows, L - 1)
    bidx = jnp.arange(B)[:, None]
    page = page_tables[bidx, safe // P]                    # (B, S)
    live = (rows < L) & (jnp.arange(S)[None, :] < qlen_v[:, None])
    page = jnp.where(live, page, 0)
    off = safe % P
    if k_scales is not None:
        kc, ks = quantized_append(cache_k, k_scales, k, page, off, live)
        vc, vs = quantized_append(cache_v, v_scales, v, page, off, live)
    else:
        kc = cache_k.at[page, off].set(
            k.reshape(B, S, -1).astype(cache_k.dtype))
        vc = cache_v.at[page, off].set(
            v.reshape(B, S, -1).astype(cache_v.dtype))
        ks = vs = None

    force_interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    if paged_attention_available(q.shape[-1], P, interpret=force_interp,
                                 dtype=kc.dtype):
        out = ragged_flash_attention(q, kc, vc, page_tables, pos_v,
                                     qlen_v, anc_mask, scale=scale,
                                     interpret=force_interp,
                                     k_scales=ks, v_scales=vs)
    else:
        out = ragged_gather_attention(q, kc, vc, page_tables, pos_v,
                                      qlen_v, anc_mask, scale=scale,
                                      k_scales=ks, v_scales=vs)
    if k_scales is not None:
        return out, kc, vc, ks, vs
    return out, kc, vc


def chain_descriptor(batch: int, window: int):
    """The default (causal-chain) ragged descriptor: every window row
    live, row i at depth i, lower-triangular visibility — exactly the
    old kpos <= qpos chunk/decode semantics. Returns
    (q_lens, depths, anc_mask) as traced-constant jnp arrays."""
    q_lens = jnp.full((batch,), window, jnp.int32)
    depths = jnp.broadcast_to(jnp.arange(window, dtype=jnp.int32),
                              (batch, window))
    anc = jnp.broadcast_to(
        jnp.tril(jnp.ones((window, window), jnp.bool_)),
        (batch, window, window))
    return q_lens, depths, anc
