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

One Pallas kernel consumes that descriptor. The grid is (batch,), with
the page table, positions and query lengths SCALAR-PREFETCHED and the
pools left in HBM: a grid step walks LIVE pages — up to the visible
horizon pos + q_len - 1, never the table's width — in blocks of
`ragged_block_pages` pages, copying each page's whole
(page_size, Hkv * D) row block from its pooled HBM location into a
double-buffered VMEM block with one DMA, the next block (or the next
walk's first) in flight while this one computes. No gathered copy of
the sequence ever materializes, and no (B, S, L) HBM mask is built
either: blocks wholly below pos take no mask, and the block(s) the
window reaches derive its visibility IN-KERNEL from `anc` via a one-hot
matmul against the block's relative positions. Padded batch entries
(q_len == 0) walk nothing and write zeros. Every kv head is computed in
the step its block arrives in, and GQA folds the q heads of one kv head
into the ROW dim (row = window row x rep + head), so a head's scores are
ONE (rows, D) x (D, keys) matmul.

ONE WALK A RUN. A chunk rides a launch as consecutive 8-row entries of
one slot, each starting where the one before ends: a RUN (`ragged_runs`
reads the runs from the table, pos, q_lens and anc the launch is handed;
nothing more is uploaded; a launch narrower than a piece or of one entry
holds none, `ragged_shares_walks`, and its kernel is the one-entry body
alone). The run's first entry walks once, from its
first visible page to the last entry's horizon, and every block it
brings in is scored against every row of the run, a row tile of whole
entries at a time, each row masked by its own cache row; the other
entries of the run do nothing. For that the launch's folded queries,
output and softmax statistics stay whole in VMEM. A decode row, a tree,
a rider behind a chunk, a padded entry is a run of one and is scored as
an entry always was. kv pages are read once per RUN.

The block size is derived at trace time from the page's bytes, the
window's rows, the table's width and the core's VMEM
(`ragged_block_pages`); there is nothing to configure.

Pool layout: FLAT-LANE pages, (num_pages, page_size, Hkv * D) — a cache
row is one token's K (or V) for every kv head side by side on the lane
dim, the same layout the flash kernels read projections in
(ops/pallas/flash_attention.py). That makes a page one contiguous HBM
region, which a single copy moves, and a kv head a 128-aligned lane
slice of the block in VMEM, which is the only way Mosaic can window one
head of a page: a block that squeezes the head out of a second-minor
(…, Hkv, D) dim is refused, and reshaping a head-minor pool at the
pallas_call boundary is a physical relayout of the whole pool under TPU
tiling, per layer per step. The append is one Hkv*D-lane row per token.

HEADS OF 64 (`head_pack`): the pool keeps 64 lanes a head, so a 128-lane
tile of a row holds TWO kv heads, and the kernel is handed the pair as one
head of 128: the queries of the pair's two groups fold into the rows as
they are, each padded with zeros over the OTHER head's 64 lanes (a score
is then q . k of its own head exactly: the zeros add nothing), and of a
row's 128 output lanes its own head's 64 are kept. The kernel itself does
not change and nothing is padded in HBM; the matrix unit contracts 128
lanes where 64 carry values, which costs it nothing a 64-deep product
would not.

The single pure-JAX fallback (`ragged_gather_attention`) gathers
``pool[page_table]`` and applies the same visibility as a materialized
(B, S, L) mask (`ragged_visibility_mask`) — it runs anywhere and is the
reference the kernel is validated against in tests/test_paged.py.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.serve_strategy import PREFILL_WINDOW_ROWS

NEG_INF = -1e30
LANES = 128
# The four PARTS an attention node names on the paged path, here, in
# paged/latent.py and in the two lowerings (ops/jax_ops.py `_mha_paged`,
# ops/latent_attention.py `paged_attention`); obs/scopes.py `ATTN_PARTS`
# lists them and `classify_serving` reads them back. An operation is its
# innermost part's.
QKV = "qkv"             # the projections, their bias, the rope
KV_WRITE = "kv_write"   # the launch's new rows into the pool
ATTEND = "attend"       # from projected rows to attended rows: descriptor
#                         arithmetic, the operands' layout, kernel or gather
OUT = "out"             # the output projection

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


def head_pack(head_dim: int) -> int:
    """kv heads the kernel takes as ONE head of 128 lanes: 2 for heads of
    64 (module docstring, "HEADS OF 64"), 1 otherwise."""
    return 2 if 2 * head_dim == LANES else 1


def paged_attention_available(head_dim: int, page_size: int,
                              interpret: bool = False,
                              dtype=jnp.float32,
                              kv_heads: Optional[int] = None) -> bool:
    """True when the ragged Pallas kernel supports these shapes on this
    backend — the ONE gate for decode, chunked prefill and tree verify
    (there is no per-variant rejection matrix any more).
    FF_TPU_NO_PAGED=1 disables the kernel everywhere (A/B runs and
    kernel-bug escape hatch, like FF_TPU_NO_FLASH). On real TPUs the
    head dim must be a lane multiple (the kernel slices one head's
    D-wide lane block out of a flat-lane page row), or 64 with an even
    number of `kv_heads`, which the kernel takes two a 128-lane tile
    (`head_pack`; the caller says how many kv heads a row holds); other
    head dims take the gather fallback, mirroring the flash bshd gate. Pages
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
    pack = head_pack(head_dim)
    if pack > 1 and (kv_heads is None or kv_heads % pack):
        return _reject(
            f"head_dim={head_dim} takes the kernel {pack} kv heads a "
            f"{LANES}-lane tile, which needs a multiple of {pack} kv heads "
            f"a row (kv_heads={kv_heads})", cfg)
    if pack > 1 and itemsize == 1:
        return _reject(
            f"head_dim={head_dim} with an 8-bit pool: the scale sidecar is "
            "a kv head's and the kernel would take two as one", cfg)
    if pack == 1 and head_dim % LANES != 0:
        return _reject(
            f"head_dim={head_dim} is neither a multiple of the "
            f"{LANES}-lane tile nor half of it", cfg)
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
                           page_size: int, window: Optional[int] = None):
    """(B, S, L) bool visibility, L = max_pages x P: the REFERENCE
    semantics both paths implement. Cache row kpos is visible to query
    row t of slot b when it is committed (kpos < pos[b]) or lies in the
    slot's in-flight window (rel = kpos - pos[b] in [0, q_lens[b])) on
    t's visibility path (anc_mask[b, t, rel]). Everything else — padded
    window rows past q_len, stale rows from earlier wider launches, the
    null page — stays masked. Chunks pass a lower-triangular anc_mask
    (causal within the chunk); trees pass the ancestor-or-self
    relation; decode is the S=1 special case of either. A sliding
    `window` layer also hides every row at or beyond `window` before
    query row t's own cache row pos[b] + t (a window layer's launches
    are causal chains: row t scores at that position), which is where
    its table may already point at the null page."""
    B, S, _ = anc_mask.shape
    L = page_tables.shape[1] * page_size
    kpos = jnp.arange(L)
    rel = jnp.broadcast_to(kpos[None, None, :] - pos[:, None, None],
                           (B, S, L))
    in_window = (rel >= 0) & (rel < q_lens[:, None, None])
    anc = jnp.take_along_axis(anc_mask, jnp.clip(rel, 0, S - 1), axis=2)
    seen = (kpos[None, None, :] < pos[:, None, None]) | (in_window & anc)
    if window is not None:
        seen &= jnp.arange(S)[None, :, None] - rel < window
    return seen


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
                            k_scales=None, v_scales=None,
                            window: Optional[int] = None):
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
    mask = ragged_visibility_mask(page_tables, pos, q_lens, anc_mask, P,
                                  window)
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
# the ragged Pallas kernel: grid (B,); per entry a double-buffered walk of
# the slot's LIVE pages in blocks, every kv head a step; page table,
# positions and query lengths prefetched; window visibility derived
# in-kernel, and only in the blocks the window reaches


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# What the block size is derived from. The K and V block buffers, double
# buffered, take this share of the core's VMEM (4 MiB of a v5e's 128:
# a quarter of the 16 MiB a kernel is scoped to by default, the rest is
# q / out / statistics and Mosaic's own score temporaries); one block's
# float32 score tile (rows x keys) is held to _SCORE_TILE_BYTES so a wide
# window shortens the block instead of spilling it.
_KV_VMEM_SHARE = 32
_SCORE_TILE_BYTES = 1 << 20
_VMEM_BYTES_ASSUMED = 128 << 20    # v4 / v5e / v5p / v6e, per core


def _vmem_capacity_bytes() -> int:
    """The attached TPU core's VMEM; off the chip (the CPU tests lower
    and interpret the same kernel) the 128 MiB every generation since
    v4 has, so a lowering test sees the block the chip will run."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except Exception:  # no TPU attached: nothing to ask
        return _VMEM_BYTES_ASSUMED


def ragged_block_pages(page_size: int, table_width: int, lane_width: int,
                       pool_dtype, q_rows: int) -> int:
    """Pages a grid step's block holds: ONE derivation for decode, chunk
    and tree windows and every pool dtype, from what a launch's trace
    can see. As many whole pages as (a) the double-buffered K and V
    block buffers fit in their VMEM share at this page's bytes
    (page_size x lane_width x itemsize), (b) keep the (q_rows, keys)
    float32 score tile within _SCORE_TILE_BYTES, and (c) the table has
    (rounded up to whole 128-key lane tiles, which (a) and (b) round
    down to where a block is that long). `q_rows` is the folded row
    count, rep x window (paged/scheduler.py counts a launch's kv_blocks
    with the same call)."""
    page_bytes = page_size * lane_width * jnp.dtype(pool_dtype).itemsize
    budget = _vmem_capacity_bytes() // _KV_VMEM_SHARE
    by_vmem = budget // (4 * page_bytes)          # K, V x two buffers
    by_score = _SCORE_TILE_BYTES // (4 * max(q_rows, 1) * page_size)
    tile = max(1, LANES // page_size)             # pages per 128 keys
    ppb = max(1, min(by_vmem, by_score))
    if ppb >= tile:
        ppb -= ppb % tile
    return max(1, min(ppb, _round_up(table_width, tile)))


# Entries a shared walk's row tile holds: a run of pieces is scored a
# tile of whole entries at a time, so one (tile rows, D) x (D, keys)
# product hands the matrix unit what the pieces handed it 8 rows at a
# time. Four, because the tile's body is straight-line code that grows
# with its rows and a program holds this kernel once a layer: at four
# entries of 32 folded rows the kernel is 1,772 bundles where the
# parent's was 1,957, at eight 2,317 for 9 % less time a row (PERF.md
# section 6). Held to _SCORE_TILE_BYTES like every other score tile.
_RUN_TILE_ENTRIES = 4


def ragged_shares_walks(entries: int, window: int) -> bool:
    """Whether a launch of this SHAPE can hold a run of several entries.
    A chunk rides as pieces of PREFILL_WINDOW_ROWS rows, the launch's
    window then; a launch with a narrower window holds one piece a slot at
    most (its window IS its widest item: analysis/shapecheck.py
    `_packed_prefill_shapes`), and one entry continues nothing. Where
    this is False `ragged_runs` merges nothing and the kernel is the
    one-entry body alone: a launch shape's program is traced, lowered and
    cached once a server, and 56 of Mistral-7B's 71 shapes are of this
    kind. What a launch that CAN share does is still read from its
    arrays."""
    return entries > 1 and window >= PREFILL_WINDOW_ROWS


def ragged_run_tile(window: int, rows: int, keys: int, entries: int) -> int:
    """Entries a row tile of a shared walk holds: _RUN_TILE_ENTRIES, or
    as many as keep the (tile rows, keys) float32 score tile within
    _SCORE_TILE_BYTES, or as the launch has; 0 where the launch's shape
    holds no shared walk (`ragged_shares_walks`)."""
    if not ragged_shares_walks(entries, window):
        return 0
    by_score = _SCORE_TILE_BYTES // (4 * rows * keys)
    return max(1, min(_RUN_TILE_ENTRIES, by_score, entries))


class _Launch(NamedTuple):
    """What `ragged_flash_attention` derives from a launch's shapes."""
    rows: int        # an entry's folded rows, padded to the sublane tile
    ppb: int         # pages a block
    tile: int        # entries a shared walk's row tile (0: none shared)
    n_rows: int      # rows of the whole-launch operands
    resident: int    # bytes the launch keeps in VMEM from start to end


def _launch_geometry(B: int, S: int, H: int, D: int, page_size: int,
                     n_pages: int, lane_width: int, pool_dtype,
                     q_dtype) -> _Launch:
    rep = H // (lane_width // D)
    q_item = jnp.dtype(q_dtype).itemsize
    rows = _round_up(rep * S, 8 * (4 // q_item))
    ppb = ragged_block_pages(page_size, n_pages, lane_width, pool_dtype,
                             rep * S)
    keys = ppb * page_size
    tile = ragged_run_tile(S, rows, keys, B)
    # a run's last row tile may reach past its end: padding behind
    n_rows = (B + max(tile, 1) - 1) * rows
    hkv = lane_width // D
    # queries, output and row positions; the three statistics; the block
    # buffers; the mask and room for a few score tiles of Mosaic's own
    resident = (2 * hkv * n_rows * D * q_item + n_rows * LANES * 4
                + hkv * n_rows * (2 * LANES + D) * 4
                + 4 * keys * lane_width * jnp.dtype(pool_dtype).itemsize
                + 6 * max(tile, 1) * rows * keys * 4)
    return _Launch(rows, ppb, tile, n_rows, resident)


# Mosaic's own temporaries beside what the launch keeps resident
_VMEM_HEADROOM = 8 << 20


def ragged_launch_fits(B: int, S: int, H: int, D: int, page_size: int,
                       n_pages: int, lane_width: int, pool_dtype,
                       q_dtype) -> bool:
    """Whether a launch's whole-array operands fit the core's VMEM: the
    folded queries, the output and the float32 statistics of EVERY entry
    stay resident (so a run's rows can share its blocks), which grows
    with entries x rows x kv heads where the per-entry blocks of old did
    not. The serving cells' launches keep 3-9 MB; a speculative launch of
    twenty 64-row trees over 8 kv heads would pass 128 MiB, and takes the
    gather fallback (`ragged_paged_attention`) instead of failing in
    Mosaic."""
    geo = _launch_geometry(B, S, H, D, page_size, n_pages, lane_width,
                           pool_dtype, q_dtype)
    return geo.resident + 2 * _VMEM_HEADROOM <= _vmem_capacity_bytes()


@jax.jit
def ragged_runs(page_tables, pos, q_lens, anc_mask):
    """(run_len, horizon), both (B,) int32: the RUNS of a launch, read
    from the descriptor it is handed. Entry b CONTINUES entry b - 1 when
    both have work, it starts where that one ends (pos[b] == pos[b-1] +
    q_lens[b-1]), their table rows are EQUAL (the pieces of one slot are
    handed one row; equal pages are equal bytes, whatever the slots are,
    and equality carries from a run's first entry to its last) and both
    are causal chains. A run is a maximal sequence of entries each
    continuing the one before: its FIRST entry carries the run's length
    and the horizon of its last entry, the others carry 0 and do nothing.
    A chunk split into 8-row pieces is one run; a decode row, a tree, a
    padded entry (length 1, horizon 0) is a run of its own, and so is
    every entry of a launch whose shape holds no run
    (`ragged_shares_walks`). Two slots over one shared prefix differ at
    the page being written and are never merged."""
    B, S, _ = anc_mask.shape
    live = q_lens > 0
    end = pos + q_lens
    if not ragged_shares_walks(B, S):
        return (jnp.ones((B,), jnp.int32),
                jnp.where(live, end, 0).astype(jnp.int32))
    tril = (lax.broadcasted_iota(jnp.int32, (S, S), 0)
            >= lax.broadcasted_iota(jnp.int32, (S, S), 1))
    ok = live & jnp.all(anc_mask == tril, axis=(1, 2))
    same = jnp.all(page_tables[1:] == page_tables[:-1], axis=1)
    cont = jnp.concatenate([
        jnp.zeros((1,), jnp.bool_),
        ok[1:] & ok[:-1] & same & (pos[1:] == end[:-1])])
    idx = jnp.arange(B, dtype=jnp.int32)
    # the first entry after b that starts a run (B past the last)
    nxt = jnp.min(jnp.where((idx[None, :] > idx[:, None]) & ~cont[None, :],
                            idx[None, :], B), axis=1)
    run_len = jnp.where(cont, 0, nxt - idx)
    last = jnp.sum(jnp.where(idx[None, :] == nxt[:, None] - 1, end[None, :],
                             0), axis=1)      # end[nxt - 1], no gather
    horizon = jnp.where(live & ~cont, last, 0)
    return run_len.astype(jnp.int32), horizon.astype(jnp.int32)


def _ragged_kernel(pt_ref, pos_ref, qlen_ref, hor_ref, run_ref, q_ref,
                   k_hbm, v_hbm, *rest, scale, page_size, ppb, rep, rows,
                   tile, quantized, sliding=None):
    """One batch entry a grid step, one WALK a run (`ragged_runs`): the
    run's first entry walks the run's live pages in blocks of `ppb`,
    each page ONE contiguous (P, Hkv * D) copy from its pooled HBM row
    into a double-buffered VMEM block, the kv heads lane slices of it,
    and scores every block against every row of the run; the run's other
    entries do nothing. The launch's queries, output and softmax
    statistics stay whole in VMEM, entry e's folded rows at e * rows (row
    = window row x rep + head of the kv group), so a head's scores are
    one (rows, D) x (D, keys) matmul over any span of entries. The next
    block — the next RUN's first block after the last — is in flight
    while this one computes; which buffer holds it survives the grid
    step in SMEM.

    A run of one entry (a decode row, a tree, a rider, a padded entry) is
    scored `rows` rows a block, its window's visibility derived from
    `anc`. A run of several (a chunk's pieces: causal chains at
    contiguous positions over the same pages) is scored a tile of `tile`
    entries at a time, each row masked by its own absolute position
    (`rowpos`); a tile past the run's end computes rows nobody reads.
    `tile` 0 is a launch whose shape holds no run of several
    (`ragged_shares_walks`): the kernel is then the one-entry body alone.

    A sliding window of `sliding` rows (a static of the call) moves the
    walk's FIRST block: it starts at the page that holds row pos -
    window + 1, the oldest row the run's first query sees, and blocks
    that hold rows older than a query's window mask them by position.
    The pages before that one are never read, and the window class of
    the pool has released them (paged/scheduler.py)."""
    if quantized:
        ks_ref, vs_ref, *rest = rest
    (rowpos_ref, anc_ref, o_ref, kbuf, vbuf, sems, par_ref, bias_scr,
     m_scr, l_scr, acc_scr) = rest
    b = pl.program_id(0)
    n_entries = pl.num_programs(0)
    n_table = pt_ref.shape[1]
    window = anc_ref.shape[1]
    keys = ppb * page_size
    n_heads, _, D = q_ref.shape
    shares = tile > 0
    tile_rows = tile * rows

    def live_pages(e):
        # pages up to the run's visible horizon; a padded entry (horizon
        # 0) walks nothing
        return jnp.minimum((hor_ref[e] + page_size - 1) // page_size,
                           n_table)

    def first_page(e):
        # the page of the oldest row run e's first query sees
        return jnp.where(
            hor_ref[e] > 0,
            jnp.maximum(pos_ref[e] - (sliding - 1), 0) // page_size, 0)

    def block_copies(e, j, buf, fn):
        """fn(copy) for the K and V copies of run e's block j."""
        first = j * ppb
        if sliding is not None:
            first = first + first_page(e)
        n = jnp.clip(live_pages(e) - first, 0, ppb)

        def one(i, _):
            page = pt_ref[e, first + i]
            dst = pl.ds(pl.multiple_of(i * page_size, page_size),
                        page_size)
            fn(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, dst],
                                     sems.at[buf]))
            fn(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, dst],
                                     sems.at[buf]))
            return 0

        lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _():
        # a block's tail past the live pages is never copied, and its
        # scores are masked by ADDING: what VMEM held before the launch
        # must not read as NaN (NaN - 1e30, 0 x NaN)
        step = 32 if keys % 32 == 0 else page_size

        def zero_rows(i, _):
            at = pl.ds(pl.multiple_of(i * step, step), step)
            zero = jnp.zeros((step, kbuf.shape[2]), kbuf.dtype)
            kbuf[0, at] = zero
            kbuf[1, at] = zero
            vbuf[0, at] = zero.astype(vbuf.dtype)
            vbuf[1, at] = zero.astype(vbuf.dtype)
            return 0

        lax.fori_loop(0, keys // step, zero_rows, 0)
        par_ref[0] = 0
        block_copies(0, 0, 0, lambda c: c.start())

    def span(e, n):
        # n folded rows from entry e's first
        return pl.ds(pl.multiple_of(e * rows, rows), n)

    # Stores of a constant are written as LOOPS (a head, a group of rows
    # a step): straight-line they are a bundle a vreg of CODE, and a
    # program holds this kernel once a layer (the compile cache and the
    # device hold a launch shape's program each: PERF.md section 6)
    def entries_heads(e, count, fn):
        """fn(head, the rows of one entry) for `count` entries from e."""
        def one(i, _):
            fn(i % n_heads, span(e + i // n_heads, rows))
            return 0

        lax.fori_loop(0, count * n_heads, one, 0)

    def reset(h, at):
        m_scr[h, at] = jnp.full((rows, LANES), NEG_INF, jnp.float32)
        l_scr[h, at] = jnp.zeros((rows, LANES), jnp.float32)
        acc_scr[h, at] = jnp.zeros((rows, D), jnp.float32)

    def mask_rows(n, fn):
        """bias_scr[r0 : r0 + step] = fn(r0, step) over the first n rows,
        `step` rows a loop step."""
        step = 32 if n % 32 == 0 else 8

        def one(i, _):
            r0 = pl.multiple_of(i * step, step)
            bias_scr[pl.ds(r0, step)] = fn(r0, step)
            return 0

        lax.fori_loop(0, n // step, one, 0)

    def unmasked(n):
        mask_rows(n, lambda r0, step: jnp.zeros((step, keys), jnp.float32))

    def attend(j, buf, e, n):
        """Fold block j (in buffer `buf`) into the statistics of the n
        rows from entry e's first, under the additive mask bias_scr[:n]."""
        at = span(e, n)
        bias = bias_scr[0:n]
        # quantized pool: the int8 page is what DMA'd from HBM and what
        # the MXU contracts; the per-page, per-head scales rode in as
        # one (Hkv, keys) row block a walk block, and dequant-on-load is
        # a row-broadcast multiply on the SCORES and the PROBABILITIES,
        # in float32 — fp K/V never exist. Otherwise compute at q's
        # dtype (a bf16 pool under an fp32 model: dot_general needs
        # matching operand dtypes)
        cdt = jnp.float32 if quantized else q_ref.dtype

        def head(h, _):
            lanes = pl.ds(pl.multiple_of(h * D, D), D)
            q = q_ref[h, at].astype(cdt)                    # (n, D)
            k = kbuf[buf, :, lanes].astype(cdt)             # (keys, D)
            v = vbuf[buf, :, lanes].astype(cdt)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks_ref[j, pl.ds(h, 1)]
            s = s + bias
            m_prev = m_scr[h, at, 0:1]
            l_prev = l_scr[h, at, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_ref[j, pl.ds(h, 1)]
            pc = p.astype(cdt)                              # in VMEM
            pv = lax.dot_general(pc, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            acc_scr[h, at] = acc_scr[h, at] * corr + pv
            m_scr[h, at] = jnp.broadcast_to(m_new, (n, LANES))
            l_scr[h, at] = jnp.broadcast_to(l_new, (n, LANES))
            return 0

        lax.fori_loop(0, n_heads, head, 0)

    def flush(h, at):
        # rows at or past q_len (rowpos -1) are forced to zero even when
        # they accumulated prefix attention: they share the entry's
        # pages, so the walk cannot skip them row-wise
        l_safe = jnp.maximum(l_scr[h, at, 0:1], 1e-30)
        o_ref[h, at] = jnp.where(rowpos_ref[at] >= 0, acc_scr[h, at] / l_safe,
                                 0.0).astype(o_ref.dtype)

    n_run = run_ref[b]

    @pl.when(n_run > 0)
    def _():
        pos = pos_ref[b]
        qlen = qlen_ref[b]
        key0 = 0
        if sliding is None:
            n_blocks = (live_pages(b) + ppb - 1) // ppb
        else:
            n_blocks = (live_pages(b) - first_page(b) + ppb - 1) // ppb
            key0 = first_page(b) * page_size
        par = par_ref[0]
        if shares:
            shared = n_run > 1
            alone = jnp.logical_not(shared)
            n_tiles = (n_run + tile - 1) // tile
            # a shared walk's last tile may reach past the run: its rows
            # are reset with the run's (and computed, and never read)
            entries_heads(b, jnp.where(shared, n_tiles * tile, 1), reset)
        else:
            alone = True
            entries_heads(b, 1, reset)

        def when(cond, fn):
            # `alone` is a Python True where nothing is shared
            fn() if cond is True else pl.when(cond)(fn)

        def tiles(fn):
            """fn(first entry, last entry) for each row tile of a shared
            walk."""
            def one(t, _):
                e0 = b + t * tile
                fn(e0, jnp.minimum(e0 + tile, b + n_run) - 1)
                return 0

            lax.fori_loop(0, n_tiles, one, 0)

        # blocks wholly below pos are all visible: the additive mask
        # stays zero until the walk reaches the window
        when(alone, lambda: unmasked(rows))

        def start_next(j, buf):
            # the run's next block, or the next run's first
            within = j + 1 < n_blocks
            nxt = b + n_run
            e = jnp.minimum(jnp.where(within, b, nxt), n_entries - 1)

            @pl.when(within | (nxt < n_entries))
            def _():
                block_copies(e, jnp.where(within, j + 1, 0), buf,
                             lambda c: c.start())

        @pl.when(n_blocks == 0)
        def _():
            start_next(-1, par)

        def entry_mask(first_key):
            """The one entry's additive mask for the block at first_key,
            from its window's `anc` relation."""
            at_window = first_key + keys > pos
            if sliding is not None:
                # a block that holds rows older than the LAST query's
                # window masks by position too; one between the two
                # masks nothing
                behind = first_key <= pos + qlen - 1 - sliding
                at_window = at_window | behind

                pl.when(jnp.logical_not(at_window))(lambda: unmasked(rows))

            @pl.when(at_window)
            def _():
                # the window's visibility without a gather and without
                # an HBM mask: column c holds cache row first_key + c,
                # window index rel[c] = first_key + c - pos. One-hot it
                # against the window rows (zeroing indices past q_len)
                # and contract with the anc relation: (anc @ onehot)[t,
                # c] = anc[t, rel[c]] when 0 <= rel[c] < q_len, else 0
                # (0 / 1 in bfloat16, one term a sum: exact).
                wrow = lax.broadcasted_iota(jnp.int32, (window, keys), 0)
                rel = first_key - pos + lax.broadcasted_iota(
                    jnp.int32, (window, keys), 1)
                onehot = ((rel == wrow)
                          & (wrow < qlen)).astype(anc_ref.dtype)
                tree_vis = lax.dot_general(
                    anc_ref[...], onehot, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) > 0.5
                col = first_key + lax.broadcasted_iota(
                    jnp.int32, (rows, keys), 1)
                seen = (col < pos) | tree_vis                # (rows, keys)
                if sliding is not None:
                    # folded row i is window row t = i // rep at cache
                    # row pos + t: seen iff pos + t - col < sliding,
                    # that is i < (col - pos + sliding) * rep
                    row = lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
                    seen = seen & (row < (col - pos + sliding) * rep)
                bias_scr[0:rows] = jnp.where(seen, 0.0, NEG_INF)

        def tile_step(j, buf, first_key, e0, e1):
            """Block j against the tile of entries e0..e1 of a run of
            chains: row r sits at cache row rowpos[r] and sees the keys
            at or before it (and within its window)."""
            lo = pos_ref[e0]
            hi = pos_ref[e1] + qlen_ref[e1] - 1
            last_key = first_key + keys - 1
            reach = first_key <= hi
            clear = last_key <= lo
            if sliding is not None:
                reach = reach & (last_key > lo - sliding)
                clear = clear & (first_key > hi - sliding)

            pl.when(reach & clear)(lambda: unmasked(tile_rows))

            def by_position(r0, step):
                at_row = rowpos_ref[pl.ds(e0 * rows + r0, step)]  # (step, 1)
                col = first_key + lax.broadcasted_iota(
                    jnp.int32, (step, keys), 1)
                seen = col <= at_row
                if sliding is not None:
                    seen = seen & (at_row - col < sliding)
                return jnp.where(seen, 0.0, NEG_INF)

            pl.when(reach & jnp.logical_not(clear))(
                lambda: mask_rows(tile_rows, by_position))

            @pl.when(reach)
            def _():
                attend(j, buf, e0, tile_rows)

        def block(j, _):
            buf = (par + j) % 2
            start_next(j, 1 - buf)
            block_copies(b, j, buf, lambda c: c.wait())
            first_key = key0 + j * keys

            def entry():
                entry_mask(first_key)
                attend(j, buf, b, rows)

            when(alone, entry)
            if shares:
                pl.when(shared)(lambda: tiles(
                    functools.partial(tile_step, j, buf, first_key)))
            return 0

        lax.fori_loop(0, n_blocks, block, 0)
        par_ref[0] = (par + n_blocks) % 2

        # finalize UNCONDITIONALLY: a padded entry that walked nothing
        # must still write (zeros), not leave its rows as garbage
        entries_heads(b, n_run, flush)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def ragged_flash_attention(q, kc_pages, vc_pages, page_tables, pos,
                           q_lens, anc_mask, *, scale: float,
                           interpret: bool = False, k_scales=None,
                           v_scales=None, window: Optional[int] = None,
                           runs=None):
    """The ragged Pallas launch. q: (B, S, H, D), D a multiple of 128 or
    64 (`head_pack`) — S is the launch's
    window width, per-entry real work is q_lens[b] <= S rows;
    kc/vc_pages: (N, P, Hkv*D) flat-lane pages (module docstring);
    page_tables: (B, max_pages); pos, q_lens: (B,); anc_mask: (B, S, S)
    bool window visibility. The page table, positions, query lengths AND
    the launch's runs (`runs`, what `ragged_runs` derives from those same
    arrays: nothing more is uploaded; derived here when the caller has
    not) ride scalar prefetch; the pools
    stay in HBM and the kernel copies each live page of a RUN itself,
    `ragged_block_pages` pages a block. The anc relation is one VMEM
    block per batch entry — the only mask state, O(B*S^2) instead of a
    (B, S, L) HBM mask. The q heads of a kv group fold into the row dim
    (row = s * rep + r), rows padded to the sublane tile and the window
    to a lane multiple so every in-kernel matmul is tile-aligned; the
    folded queries and the output are whole in VMEM, entry after entry
    along the rows, with `tile - 1` entries of padding behind the last
    (a run's last row tile may reach past its end), and a launch whose
    resident operands do not fit the core's VMEM is refused by name
    (`ragged_launch_fits`; `ragged_paged_attention` falls back before it
    gets here). For a quantized pool,
    k_scales/v_scales are the (N, Hkv) sidecar: the table-mapped scales
    are gathered here and repeated along each page's rows (B * max_pages
    * Hkv * P floats, what the per-page blocks held before), one
    (Hkv, keys) block a walk block; a run's are its first entry's. Rows
    at or past q_lens[b] output zeros. Jitted so that the layers of a
    model trace and lower ONE kernel a launch shape (two where window and
    full layers mix: `window` is a static of the call)."""
    B, S, H, D = q.shape
    P = kc_pages.shape[1]
    Hkv = kc_pages.shape[2] // D
    if head_pack(D) > 1:
        # heads of 64: two kv heads a 128-lane tile (module docstring)
        out = ragged_flash_attention(
            _pack_heads(q, Hkv), kc_pages, vc_pages, page_tables, pos,
            q_lens, anc_mask, scale=scale, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, window=window, runs=runs)
        return _unpack_heads(out, Hkv)
    rep = H // Hkv
    n_pages = page_tables.shape[1]
    shape = (B, S, H, D, P, n_pages, Hkv * D, kc_pages.dtype, q.dtype)
    rows, ppb, tile, n_rows, resident = _launch_geometry(*shape)
    wcols = _round_up(S, LANES)     # the window, a whole lane tile
    keys = ppb * P
    if not interpret and not ragged_launch_fits(*shape):
        raise ValueError(
            f"a ragged launch of {B} entries x {rows} folded rows x {Hkv} "
            f"kv heads keeps {resident >> 20} MiB in VMEM, the core has "
            f"{_vmem_capacity_bytes() >> 20}: take the gather path "
            "(ragged_paged_attention does)")
    quantized = k_scales is not None
    if quantized and window is not None:
        raise ValueError("a window layer's pool is not quantized: the "
                         "scale blocks follow the table from its start")
    pos = pos.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    run_len, horizon = runs or ragged_runs(page_tables, pos, q_lens,
                                           anc_mask)
    # (B, S, Hkv, rep, D) -> (Hkv, B * rows, D): a kv group's heads are
    # adjacent rows of one tile, an entry's tiles adjacent too
    qr = q.reshape(B, S, Hkv, rep, D).transpose(2, 0, 1, 3, 4).reshape(
        Hkv, B, S * rep, D)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - S * rep), (0, 0))
                 ).reshape(Hkv, B * rows, D)
    # the cache row each folded row sits at; -1 where it is no work
    i = jnp.arange(rows, dtype=jnp.int32)[None, :]
    rowpos = jnp.where(i < q_lens[:, None] * rep, pos[:, None] + i // rep,
                       -1).reshape(B * rows, 1)
    if n_rows > B * rows:
        behind = (0, n_rows - B * rows)
        qr = jnp.pad(qr, ((0, 0), behind, (0, 0)))
        rowpos = jnp.pad(rowpos, (behind, (0, 0)), constant_values=-1)
    anc_f = jnp.pad(
        jnp.repeat(anc_mask, rep, axis=1).astype(jnp.bfloat16),
        ((0, 0), (0, rows - S * rep), (0, wcols - S)))

    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = [
        whole,
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qr, kc_pages, vc_pages]
    if quantized:
        n_blocks = -(-n_pages // ppb)
        for sc in (k_scales, v_scales):   # trace-time, K then V
            rows_sc = jnp.pad(sc[page_tables],               # (B, pages, Hkv)
                              ((0, 0), (0, n_blocks * ppb - n_pages),
                               (0, 0)))
            rows_sc = rows_sc.reshape(B, n_blocks, ppb, Hkv).transpose(
                0, 1, 3, 2)
            operands.append(jnp.broadcast_to(
                rows_sc[..., None],
                (B, n_blocks, Hkv, ppb, P)).reshape(B, n_blocks, Hkv, keys))
            in_specs.append(pl.BlockSpec((None, n_blocks, Hkv, keys),
                                         lambda b, *_: (b, 0, 0, 0)))
    in_specs += [whole, pl.BlockSpec((None, rows, wcols),
                                     lambda b, *_: (b, 0, 0))]
    operands += [rowpos, anc_f]

    scratch = [
        pltpu.VMEM((2, keys, Hkv * D), kc_pages.dtype),
        pltpu.VMEM((2, keys, Hkv * D), vc_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((max(tile, 1) * rows, keys), jnp.float32),
        pltpu.VMEM((Hkv, n_rows, LANES), jnp.float32),
        pltpu.VMEM((Hkv, n_rows, LANES), jnp.float32),
        pltpu.VMEM((Hkv, n_rows, D), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=in_specs,
        out_specs=whole,
        scratch_shapes=scratch,
    )
    static = {} if window is None else {"sliding": int(window)}
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, page_size=P,
                          ppb=ppb, rep=rep, rows=rows, tile=tile,
                          quantized=quantized, **static),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, n_rows, D), q.dtype),
        # the walk carries its buffer parity and an in-flight copy from
        # one entry to the next: the grid is a sequence
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, resident + _VMEM_HEADROOM)),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_tables.astype(jnp.int32), pos, q_lens, horizon, run_len,
      *operands)
    return out[:, :B * rows].reshape(Hkv, B, rows, D)[:, :, :S * rep].reshape(
        Hkv, B, S, rep, D).transpose(1, 2, 0, 3, 4).reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# the ONE lowering entry: rope + page write + attend, for every variant


def ragged_paged_attention(q, k, v, cache_k, cache_v, page_tables, pos,
                           q_lens, depths, anc_mask, *, scale: float,
                           rope_theta: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           window: Optional[int] = None,
                           rope_scaling=None):
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

    `window` makes this a sliding-window layer: `page_tables` is then
    the WINDOW class's table, whose entries behind a request's window
    are the null page, and neither path reads them (the kernel's walk
    starts at the window, the gather masks by position). `rope_scaling`
    is the op's YaRN tuple (ops/jax_ops.py apply_rope).

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
        with jax.named_scope(QKV):
            positions = pos_v[:, None] + depths                # (B, S)
            q = apply_rope(q, rope_theta, pos_offset=positions,
                           scaling=rope_scaling)
            k = apply_rope(k, rope_theta, pos_offset=positions,
                           scaling=rope_scaling)
    with jax.named_scope(KV_WRITE):
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
    D = q.shape[-1]
    pack = head_pack(D)
    shape = (B, S, q.shape[2], pack * D, P, page_tables.shape[1],
             kc.shape[2], kc.dtype, q.dtype)
    kernel = paged_attention_available(D, P, interpret=force_interp,
                                       dtype=kc.dtype,
                                       kv_heads=kc.shape[2] // D)
    if kernel and not force_interp and not ragged_launch_fits(*shape):
        kernel = _reject(
            f"a launch of {B} entries x {S} rows keeps more in VMEM than "
            "the core has",
            (q.shape[-1], P, kc.dtype.name, jax.default_backend()))
    with jax.named_scope(ATTEND):
        if kernel:
            # derived HERE, in the step's own trace, where the layers of
            # a launch are handed the same arrays: XLA merges their equal
            # derivations into one a launch (inside the kernel's jit each
            # layer would run its own)
            out = ragged_flash_attention(
                q, kc, vc, page_tables, pos_v, qlen_v, anc_mask,
                scale=scale, interpret=force_interp, k_scales=ks,
                v_scales=vs, window=window,
                runs=ragged_runs(page_tables, pos_v, qlen_v, anc_mask))
        else:
            out = ragged_gather_attention(q, kc, vc, page_tables, pos_v,
                                          qlen_v, anc_mask, scale=scale,
                                          k_scales=ks, v_scales=vs,
                                          window=window)
    if k_scales is not None:
        return out, kc, vc, ks, vs
    return out, kc, vc


def _own_half(heads: int, kv_heads: int):
    """(heads,) bool: whether a q head's kv head is the FIRST of its pair
    (`head_pack`): q heads lie in the order of their kv heads."""
    return (jnp.arange(heads) // (heads // kv_heads)) % 2 == 0


def _pack_heads(q, kv_heads: int):
    """(B, S, H, 64) -> (B, S, H, 128): a head's queries over its own kv
    head's lanes of the pair's tile, zeros over the other's."""
    first = _own_half(q.shape[2], kv_heads)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(first, q, zero),
                            jnp.where(first, zero, q)], axis=-1)


def _unpack_heads(out, kv_heads: int):
    """(B, S, H, 128) -> (B, S, H, 64): of a row's output over the pair's
    tile, its own head's lanes."""
    d = out.shape[-1] // 2
    return jnp.where(_own_half(out.shape[2], kv_heads)[:, None],
                     out[..., :d], out[..., d:])


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
