"""Every Pallas entry point at the chip smoke's shapes, one catalog, two uses.

  * tier-1 (CPU, tests/test_tpu_lowering.py): each case is lowered for TPU
    with `jax.export` — the Pallas->Mosaic lowering runs on any host, so a
    block-shape refusal is caught before chip time is spent;
  * on the chip (`python tools/chip_kernels.py`, through the chip tool): each
    case is compiled by Mosaic, run, and compared with its plain-XLA
    reference. Exit code 1 if any case fails. What the Mosaic compiler itself
    accepts only this run can say.

Shapes: Llama-3-8B attention width (32 q / 8 kv heads x 128) for the ragged
kernel and the D=128 flash kernels, plus the D=64 padded flash path; and the
benchmark's own serving launches (`benchmark/configs/mistral-7b-serve1.json`:
8 slots, page 64, bf16 pool, table 64 wide) as `ragged_bench_*`.

The latent (MLA) kernel and the grouped expert kernels run at
Mistral-Small-4's widths (`mla_*`, `moe_grouped_*`), the first also at the
new cell's launches (`mla_bench_*`) and at Ling-3.0-flash's 576-value row
(`mla_wide_*`); the delta-rule scan at Ling-3.0-flash's widths and its
cell's launches (`kda_*`); the state-space scan at Granite-4.0-H-Micro's
widths and its cell's launches (`ssd_*`), whose attention launches, heads
of 64, are `ragged_bench_g64_*`.

The expert router's choice (`route_*`; ops/expert_share.py `_select` and
`_top_k`) is plain XLA, no Pallas kernel: its cases are `route_cases()`,
outside the lowering test's catalog, at the three expert cells' launch
rows, against the form that sorts (`select_by_sort`, `lax.top_k`), to the
bit.

`--time` also prints each ragged, latent and grouped case's device
microseconds a call (the kernel's own events in a profiler trace), for a
before / after; for a `route_*` case the whole program's, beside the
sorting form's.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

H, HKV, D = 32, 8, 128
# page sizes the availability gate admits per pool dtype (one sublane tile
# and one MXU-wide page each)
RAGGED_POOLS = (("bfloat16", "bfloat16", 16), ("bfloat16", "bfloat16", 128),
                ("float32", "float32", 8), ("bfloat16", "int8", 32),
                ("bfloat16", "int8", 128))
RAGGED_WINDOWS = (("decode", 1), ("chunk", 8), ("chunk", 64), ("tree", 8))


def _tree_anc(S):
    from flexflow_tpu.spec.tree import ancestor_masks

    parents = np.full((S,), -1, np.int32)
    parents[:min(S, 6)] = np.array([-1, 0, 1, 2, 1, 0], np.int32)[:S]
    return ancestor_masks(parents[None])[0]


def _ragged_fns(S, window=None, scale=None):
    """(kernel fn, reference fn) over one argument list
    (q, kc, vc, pt, pos, q_lens, anc[, k_scales, v_scales])."""
    from flexflow_tpu.paged.attention import (
        ragged_flash_attention,
        ragged_gather_attention,
    )

    scale = 1.0 / np.sqrt(D) if scale is None else scale

    def run(impl):
        def fn(q, kc, vc, pt, pos, q_lens, anc, *sc):
            skw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
            out = impl(q, kc, vc, pt, pos, q_lens, anc, scale=scale,
                       window=window, **skw)
            # rows at or past q_len are garbage by contract on both paths
            live = jnp.arange(S)[None, :] < q_lens[:, None]
            return jnp.where(live[..., None, None], out, 0)
        return fn

    return run(ragged_flash_attention), run(ragged_gather_attention)


def _ragged_case(kind, S, qdt, pdt, P, seed=0):
    """(fn, args, ref_fn) for one ragged launch: 4 entries (two live at
    different depths, one short, one padded) over a shuffled page table."""
    from flexflow_tpu.paged.quant import quantized_append

    B, MAXP = 4, max(4, -(-(96 + S) // P))
    N = B * MAXP + 1
    rs = np.random.RandomState(seed)
    quant = pdt == "int8"
    q = jnp.asarray(rs.randn(B, S, H, D), qdt)
    pt = jnp.asarray((rs.permutation(N - 1)[:B * MAXP] + 1)
                     .reshape(B, MAXP).astype(np.int32))
    pos = jnp.asarray(np.array([90, 37, 5, 0], np.int32))
    q_lens = jnp.asarray(np.array([S, S, max(1, S // 2), 0], np.int32))
    if kind == "tree":
        anc = np.tile(_tree_anc(S), (B, 1, 1))
    else:
        anc = np.tile(np.tril(np.ones((S, S), bool)), (B, 1, 1))
    anc = jnp.asarray(anc)
    scales = ()
    if quant:
        # a real quantized pool: append random rows page by page
        rows = jnp.asarray(rs.randn(N, P, HKV, D), jnp.float32)
        page = jnp.broadcast_to(jnp.arange(N)[:, None], (N, P))
        off = jnp.broadcast_to(jnp.arange(P)[None], (N, P))
        pools = []
        for x in (rows, rows[::-1]):
            pool, sc = quantized_append(
                jnp.zeros((N, P, HKV * D), jnp.int8),
                jnp.zeros((N, HKV), jnp.float32), x, page, off,
                jnp.ones((N, P), bool))
            pools.append(pool)
            scales += (sc,)
        kc, vc = pools
    else:
        kc = jnp.asarray(rs.randn(N, P, HKV * D), pdt)
        vc = jnp.asarray(rs.randn(N, P, HKV * D), pdt)
    fn, ref = _ragged_fns(S)
    return fn, (q, kc, vc, pt, pos, q_lens, anc) + scales, ref


# the benchmark's serving launches: (window, [(slot, pos, q_len)]). A
# decode tick is one row a slot; a 64-token chunk rides ONE packed launch
# as eight 8-row pieces of the same slot (paged/scheduler.py), all
# sharing ONE walk of the prefix below them (a run, paged/attention.py); a
# padded entry has q_len 0. `chunk128` is Mellum2's launch
# (benchmark/configs/mellum2-12b-serve1.json: 4 kv heads, a table 516 wide):
# a 128-token chunk as 16 pieces at position 6,200 and three decode rows
# behind it, in a full layer and in a sliding one.
BENCH_LAUNCHES = {
    "decode": (1, [(i, p, 1) for i, p in enumerate(
        (100, 180, 250, 330, 400, 470, 560, 639))]),
    "chunk64": (8, [(0, 3500 + 8 * i, 8) for i in range(8)]),
    "packed": (8, [(0, 1500 + 8 * i, 8) for i in range(4)]
               + [(1, 0, 5), (2, 63, 1), (3, 200, 3), (4, 0, 0)]),
}
_CHUNK128 = (8, [(0, 6200 + 8 * i, 8) for i in range(16)]
             + [(1, 3000, 1), (2, 9000, 1), (3, 500, 1)])
BENCH_LAUNCHES["chunk128_full"] = _CHUNK128
BENCH_LAUNCHES["chunk128_window"] = _CHUNK128
# kind -> (kv heads, table width, pool pages, sliding window)
BENCH_GEOMETRY = {"chunk128_full": (4, 516, 320, None),
                  "chunk128_window": (4, 516, 320, 1024)}
# Granite-4.0-H-Micro's launches (benchmark/configs/
# granite-4.0-h-micro-serve1.json: 32 slots, 8 kv heads of 64, a table 49
# wide, scores times 1/64): 32 decode rows over 300-2,900-row contexts, and
# a 512-row chunk as 64 pieces of one slot beside 31 decode rows and a
# filler. The kernel takes two kv heads a 128-lane tile.
_DECODE32 = [(i, 300 + 84 * i, 1) for i in range(32)]
BENCH_LAUNCHES["g64_decode32"] = (1, _DECODE32)
BENCH_LAUNCHES["g64_chunk512"] = (
    8, [(0, 1024 + 8 * i, 8) for i in range(64)] + [(0, 0, 0)]
    + _DECODE32[1:])
HEADS_OF_64 = ("g64_decode32", "g64_chunk512")


def _bench_case(kind, seed=0):
    """(fn, args, ref_fn) for one launch of the benchmark's server: page
    64, bfloat16 pool and q, a table 64 pages wide whose entries past a
    slot's live pages are the null page, as the server leaves them."""
    P = 64
    hkv, MAXP, N, window = BENCH_GEOMETRY.get(kind, (HKV, 64, 128, None))
    d, scale = D, None
    if kind in HEADS_OF_64:
        d, MAXP, N, scale = 64, 49, 1600, 1.0 / 64
    S, entries = BENCH_LAUNCHES[kind]
    B = len(entries)
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, S, H, d), jnp.bfloat16)
    kc = jnp.asarray(rs.randn(N, P, hkv * d), jnp.bfloat16)
    vc = jnp.asarray(rs.randn(N, P, hkv * d), jnp.bfloat16)
    free = list(rs.permutation(N - 1) + 1)
    tables = {}
    for slot, p, ql in entries:
        live = -(-(p + ql) // P)
        row = tables.setdefault(slot, np.zeros((MAXP,), np.int32))
        for i in range(live):
            if row[i] == 0:
                row[i] = free.pop()
    pt = jnp.asarray(np.stack([tables[slot] for slot, _, _ in entries]))
    pos = jnp.asarray(np.array([p for _, p, _ in entries], np.int32))
    q_lens = jnp.asarray(np.array([ql for _, _, ql in entries], np.int32))
    anc = jnp.asarray(np.tile(np.tril(np.ones((S, S), bool)), (B, 1, 1)))
    fn, ref = _ragged_fns(S, window, scale)
    return fn, (q, kc, vc, pt, pos, q_lens, anc), ref


# the latent (MLA) kernel at Mistral-Small-4's widths: 32 heads over one
# [c_kv (256) | k_r (64)] row a token, 384 lanes, values the first 256
MLA_HEADS, MLA_WIDTH, MLA_VALUE = 32, 320, 256
MLA_POOLS = (("bfloat16", 16), ("bfloat16", 64), ("bfloat16", 128),
             ("float32", 64))
MLA_WINDOWS = (("decode", 1), ("chunk", 8), ("tree", 8))
# the new cell's launches (benchmark/configs/mistral-small-4-serve1.json:
# 8 slots, page 64, table 196 wide): a decode tick over 4-12k prefixes and
# a 256-token chunk as 32 8-row pieces of one slot over an 8k prefix
MLA_BENCH = {
    "decode": (1, [(i, p, 1) for i, p in enumerate(
        (4100, 5200, 6300, 7400, 8500, 9600, 11000, 12400))]),
    "chunk256": (8, [(0, 8192 + 8 * i, 8) for i in range(32)]),
}


def _mla_fns(S):
    from flexflow_tpu.paged.latent import (
        latent_flash_attention,
        latent_gather_attention,
    )

    def run(impl):
        def fn(q, pool, pt, pos, q_lens, anc):
            out = impl(q, pool, pt, pos, q_lens, anc, value_lanes=MLA_VALUE)
            live = jnp.arange(S)[None, :] < q_lens[:, None]
            return jnp.where(live[..., None, None], out, 0)
        return fn

    return run(latent_flash_attention), run(latent_gather_attention)


def _mla_rows(rs, shape, dt):
    """Random rows whose pad lanes (320..383) are zero, as the program's
    appends leave them; scaled so that scores over 320 lanes stay O(1)."""
    x = rs.randn(*shape).astype(np.float32) * MLA_WIDTH ** -0.25
    x[..., MLA_WIDTH:] = 0
    return jnp.asarray(x, dt)


def _mla_case(kind, S, dt, P, seed=0):
    B, MAXP = 4, max(4, -(-(96 + S) // P))
    N = B * MAXP + 1
    rs = np.random.RandomState(seed)
    q = _mla_rows(rs, (B, S, MLA_HEADS, 384), dt)
    pool = _mla_rows(rs, (N, P, 384), dt)
    pt = jnp.asarray((rs.permutation(N - 1)[:B * MAXP] + 1)
                     .reshape(B, MAXP).astype(np.int32))
    pos = jnp.asarray(np.array([90, 37, 5, 0], np.int32))
    q_lens = jnp.asarray(np.array([S, S, max(1, S // 2), 0], np.int32))
    anc = (np.tile(_tree_anc(S), (B, 1, 1)) if kind == "tree"
           else np.tile(np.tril(np.ones((S, S), bool)), (B, 1, 1)))
    fn, ref = _mla_fns(S)
    return fn, (q, pool, pt, pos, q_lens, jnp.asarray(anc)), ref


def _mla_bench_case(kind, seed=0):
    P, MAXP, N = 64, 196, 1600
    S, entries = MLA_BENCH[kind]
    B = len(entries)
    rs = np.random.RandomState(seed)
    q = _mla_rows(rs, (B, S, MLA_HEADS, 384), jnp.bfloat16)
    pool = _mla_rows(rs, (N, P, 384), jnp.bfloat16)
    free = list(rs.permutation(N - 1) + 1)
    tables = {}
    for slot, p, ql in entries:
        row = tables.setdefault(slot, np.zeros((MAXP,), np.int32))
        for i in range(-(-(p + ql) // P)):
            if row[i] == 0:
                row[i] = free.pop()
    pt = jnp.asarray(np.stack([tables[slot] for slot, _, _ in entries]))
    pos = jnp.asarray(np.array([p for _, p, _ in entries], np.int32))
    q_lens = jnp.asarray(np.array([ql for _, _, ql in entries], np.int32))
    anc = jnp.asarray(np.tile(np.tril(np.ones((S, S), bool)), (B, 1, 1)))
    fn, ref = _mla_fns(S)
    return fn, (q, pool, pt, pos, q_lens, anc), ref


def _moe_case(tokens, seed=0):
    """The grouped expert kernels at Mistral-Small-4's widths (32 held of
    128 experts, top 4, 4096 x 2048) for one launch of `tokens` rows,
    against a dense loop over the held experts."""
    from flexflow_tpu.ops.pallas import grouped_experts as ge

    G, K, d, f = 32, 4, 4096, 2048
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(tokens, d), jnp.bfloat16)
    ids = jnp.asarray(np.stack([rs.permutation(128)[:K]
                                for _ in range(tokens)]).astype(np.int32))
    # one random matrix a kind, rolled by a different amount for each
    # expert: 32 distinct experts without drawing 800 M normals
    w = [jnp.stack([jnp.roll(base, 17 * g, axis=1) for g in range(G)])
         for base in (jnp.asarray(rs.randn(a, b) * a ** -0.5, jnp.bfloat16)
                      for a, b in ((d, f), (d, f), (f, d)))]
    local = jnp.where(ids < G, ids, G).reshape(-1)
    tm = ge.row_tile(tokens * K, G, x.dtype)
    rows = ge.num_tiles(tokens * K, G, tm) * tm

    def fn(x, local, wg, wu, wd):
        dest, tile_group, n_active, _ = ge.layout(local, G, tm)
        token = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), K)
        src = jnp.full((rows,), tokens, jnp.int32).at[dest].set(
            token, mode="drop")
        xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
        h = ge.grouped_swiglu(xs, wg, wu, tile_group, n_active, tm=tm)
        y = ge.grouped_dot(h, wd, tile_group, n_active, tm=tm,
                           out_dtype=jnp.float32)
        per = jnp.where((local < G)[:, None],
                        jnp.take(y, jnp.minimum(dest, rows - 1), axis=0), 0)
        return per.reshape(tokens, K, d).sum(1)

    def ref(x, local, wg, wu, wd):
        hit = (local.reshape(tokens, K, 1) == jnp.arange(G)).any(1)
        out = jnp.zeros((tokens, d), jnp.float32)
        for g in range(G):
            a = jnp.dot(x, wg[g], preferred_element_type=jnp.float32)
            b = jnp.dot(x, wu[g], preferred_element_type=jnp.float32)
            h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
            out = out + hit[:, g:g + 1] * jnp.dot(
                h, wd[g], preferred_element_type=jnp.float32)
        return out

    return fn, (x, local, *w), ref


def _mla_wide_case(seed=0):
    """The latent kernel at Ling-3.0-flash's row: [c_kv (512) | k_r (64)]
    in 640 lanes, values the first 512; a 64-row chunk as eight 8-row
    pieces of one slot over a 2k prefix, beside two decode-like pieces."""
    from flexflow_tpu.paged.latent import (
        latent_flash_attention,
        latent_gather_attention,
    )

    P, MAXP, S, width, lanes, value = 64, 40, 8, 576, 640, 512
    entries = [(0, 2048 + 8 * i, 8) for i in range(8)] + [(1, 700, 1),
                                                          (2, 0, 0)]
    B, N = len(entries), 3 * MAXP + 1
    rs = np.random.RandomState(seed)

    def rows(shape):
        x = rs.randn(*shape).astype(np.float32) * width ** -0.25
        x[..., width:] = 0
        return jnp.asarray(x, jnp.bfloat16)

    q, pool = rows((B, S, MLA_HEADS, lanes)), rows((N, P, lanes))
    tables = 1 + np.arange(3 * MAXP, dtype=np.int32).reshape(3, MAXP)
    pt = jnp.asarray(tables[[slot for slot, _, _ in entries]])
    pos = jnp.asarray(np.array([p for _, p, _ in entries], np.int32))
    q_lens = jnp.asarray(np.array([ql for _, _, ql in entries], np.int32))
    anc = jnp.asarray(np.tile(np.tril(np.ones((S, S), bool)), (B, 1, 1)))

    def run(impl):
        def fn(q, pool, pt, pos, q_lens, anc):
            out = impl(q, pool, pt, pos, q_lens, anc, value_lanes=value)
            live = jnp.arange(S)[None, :] < q_lens[:, None]
            return jnp.where(live[..., None, None], out, 0)
        return fn

    return (run(latent_flash_attention), (q, pool, pt, pos, q_lens, anc),
            run(latent_gather_attention))


def _kda_case(kind, seed=0):
    """The delta-rule scan (`kda_ragged_scan`) at Ling-3.0-flash's widths,
    32 heads of 128 x 128 float32 state over 8 slots. "chunk512" is a
    launch of the benchmark's cell as the server composes it: a 512-row
    chunk as 64 pieces of one slot, one filler item without rows, then 7
    other slots' decode rows riding (72 items); "chunk256" the same with
    32 pieces and no filler; "strongdecay" is chunk256 at the strongest
    decay the gate admits (a = -5 on every live row and channel); "decode"
    is 8 one-row items, two of them without rows. Against the scan over
    items and rows (ops/kda_attention.py `scan_items`) at the HIGHEST
    matmul precision: a float32 product taken in one bfloat16 pass inside
    the kernel then shows as an error (`KDA_TOL`)."""
    from flexflow_tpu.ops import kda_attention as kda
    from flexflow_tpu.ops.pallas import kda_scan

    H, d, N, W = 32, 128, 8, kda_scan.ROWS
    if kind != "decode":
        pieces, filler = (64, [(3, 0, 0)]) if kind == "chunk512" else (32, [])
        items = [(3, 4096 + 8 * i, 8) for i in range(pieces)] + filler + [
            (s, 900 + 11 * s, 1) for s in (0, 1, 2, 4, 5, 6, 7)]
    else:
        items = [(s, 0 if s == 5 else 50 + s, 0 if s in (2, 6) else 1)
                 for s in range(8)]
    B = len(items)
    slots, pos, q_lens = (jnp.asarray(np.array(col, np.int32))
                          for col in zip(*items))
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (B, W, H, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, W, H, d)) - 4)
    if kind == "strongdecay":
        a = jnp.full_like(a, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, W, H)))
    alive = jnp.arange(W)[None, :] < q_lens[:, None]
    a = jnp.where(alive[:, :, None, None], a, 0.0)
    beta = jnp.where(alive[:, :, None], beta, 0.0)
    state = jax.random.normal(ks[5], (N, H, d, d))

    def fn(q, k, v, a, beta, state):
        slot, start, fresh, _ = kda.item_chain(slots, pos, q_lens)
        flat = lambda t: t.reshape(B, W, H * d)            # noqa: E731
        o, s = kda_scan.kda_ragged_scan(
            flat(q), flat(k), flat(k * beta[..., None]), flat(v), flat(a),
            state, slot, start.astype(jnp.int32), fresh.astype(jnp.int32),
            q_lens, heads=H)
        return jnp.where(alive[:, :, None, None],
                         o.reshape(B, W, H, d), 0), s

    def ref(q, k, v, a, beta, state):
        with jax.default_matmul_precision("highest"):
            o, s = kda.scan_items(q, k, v, a, beta,
                                  kda.item_chain(slots, pos, q_lens), state)
        return jnp.where(alive[:, :, None, None], o, 0), s

    return fn, (q, k, v, a, beta, state), ref


def _ssd_case(kind, seed=0):
    """The state-space scan (`ssd_ragged_scan`) at Granite-4.0-H-Micro's
    widths, 64 heads of a 64 x 128 float32 state over 32 slots, called as
    ops/mamba2.py `paged_mixer` calls it. "decode" is the cell's usual
    launch, ONE row wide: 32 one-row items, two of them without rows (the
    one-row kernel, as many heads a step as `_heads_a_step` derives);
    "chunk512" a chunk's, eight rows wide: 64 pieces of one slot, a filler
    item without rows, 31 other slots' decode rows (96 items: the solve
    and the one-row form in one launch). Log-decays as
    the builder's draw gives them (-0.01 to -1.6 a step); "weakdecay" is
    chunk512 at -0.01 a step on every live row and head, "strongdecay" at
    -30 (exp(-30) is 1e-13: a state forgotten every step, which the
    kernel's differences of running sums must survive where exp(-G) would
    overflow). Against the recurrence row by row in FLOAT64 ON THE HOST
    (`_ssd_rows_float64`), not the float32 scan over items and rows the
    CPU tests use (ops/mamba2.py `scan_items`): at -0.01 a step that scan
    multiplies a state by the same `exp(-0.01)` 520 times, so the rounding
    of the chip's ONE exponential adds up coherently (the two float32
    forms read 4.7e-5 apart there, my chip run, PR 51, where every other
    case read 1e-7 to 1.5e-6), and a float32 oracle cannot say which of
    the two is off (`KDA_TOL`)."""
    from flexflow_tpu.ops.pallas import ssd_scan
    from flexflow_tpu.ops.slot_state import item_chain

    H, P, N, S = 64, 64, 128, 32
    if kind == "decode":
        W = 1
        items = [(s, 0 if s == 5 else 300 + 84 * s, 0 if s in (2, 6) else 1)
                 for s in range(S)]
    else:
        W = ssd_scan.ROWS
        items = [(3, 1024 + 8 * i, 8) for i in range(64)] + [(3, 0, 0)] + [
            (s, 300 + 84 * s, 1) for s in range(S) if s != 3]
    B = len(items)
    slots, pos, q_lens = (jnp.asarray(np.array(col, np.int32))
                          for col in zip(*items))
    ks = jax.random.split(jax.random.key(seed), 6)
    xh = jax.random.normal(ks[0], (B, W, H, P))
    b_in, c_out = (jax.random.normal(k, (B, W, N)) for k in ks[1:3])
    dt = jnp.exp(jax.random.uniform(ks[3], (B, W, H), minval=np.log(0.01),
                                    maxval=np.log(0.1)))
    a = -dt * jnp.exp(jax.random.uniform(ks[4], (B, W, H), minval=0.0,
                                         maxval=np.log(16.0)))
    if kind in ("weakdecay", "strongdecay"):
        a = jnp.full_like(a, -0.01 if kind == "weakdecay" else -30.0)
    alive = (jnp.arange(W)[None, :] < q_lens[:, None])[:, :, None]
    dt, a = jnp.where(alive, dt, 0.0), jnp.where(alive, a, 0.0)
    state = jax.random.normal(ks[5], (S, H, P, N))

    def fn(xh, b_in, c_out, dt, a, state):
        slot, start, fresh, _ = item_chain(slots, pos, q_lens)
        y, s = ssd_scan.ssd_ragged_scan(
            (dt[..., None] * xh).reshape(B, W, H * P), b_in, c_out, a,
            state, slot, start.astype(jnp.int32), fresh.astype(jnp.int32),
            q_lens, heads=H)
        return jnp.where(alive[..., None], y.reshape(B, W, H, P), 0), s

    def ref(xh, b_in, c_out, dt, a, state):
        shapes = (jax.ShapeDtypeStruct(xh.shape, jnp.float32),
                  jax.ShapeDtypeStruct(state.shape, jnp.float32))
        return jax.pure_callback(
            lambda *t: _ssd_rows_float64(items, *t), shapes,
            xh, b_in, c_out, dt, a, state)

    return fn, (xh, b_in, c_out, dt, a, state), ref


def _ssd_heads_a_step(kind):
    """Heads a grid step of `_ssd_case(kind)`'s launch, as the module
    derives them from the launch's width."""
    from flexflow_tpu.ops.pallas import ssd_scan

    return ssd_scan._heads_a_step(64, 64, 128,
                                 1 if kind == "decode" else ssd_scan.ROWS)


def _ssd_rows_float64(items, xh, b_in, c_out, dt, a, state):
    """S_t = exp(a_t) S_t-1 + (D_t x_t) B_t^T, y_t = S_t C_t, a live row
    at a time in numpy float64; `items` are (slot, first row, live rows),
    a request's row 0 starts from zero. Dead rows read out zero."""
    xh, b_in, c_out, dt, a = (np.asarray(t, np.float64)
                              for t in (xh, b_in, c_out, dt, a))
    s = np.asarray(state, np.float64).copy()
    y = np.zeros(xh.shape, np.float64)
    for i, (slot, first, rows) in enumerate(items):
        for t in range(rows):
            if first + t == 0:
                s[slot] = 0.0
            s[slot] = (np.exp(a[i, t])[:, None, None] * s[slot]
                       + (dt[i, t][:, None] * xh[i, t])[:, :, None]
                       * b_in[i, t][None, None, :])
            y[i, t] = s[slot] @ c_out[i, t]
    return y.astype(np.float32), s.astype(np.float32)


# the three routers at a full launch's rows (a chunk's pieces, a filler
# where the server fills, the other slots' riders, 8 rows an item):
# name -> (rows, ExpertShareAttrs fields)
ROUTERS = {
    "ling512": (576, dict(n_experts=512, k=8, routed_scale=2.5,
                          score="sigmoid", n_group=8, topk_group=4,
                          select_bias=True)),
    "small4_128": (312, dict(n_experts=128, k=4)),
    "mellum2_64": (152, dict(n_experts=64, k=8)),
}


def select_by_sort(attrs, scores, bias):
    """ops/expert_share.py `_select` as it was through PR 45: every choice
    a `lax.top_k`, which the TPU lowers to a sort of the whole axis. The
    oracle of the rounds that replaced it (tests/
    test_expert_share_select.py) and what `--time` sets them against."""
    if bias is None and attrs.n_group == 1:
        return lax.top_k(scores, attrs.k)
    T, E = scores.shape
    chosen_by = scores if bias is None else scores + bias.astype(
        jnp.float32)
    if attrs.n_group > 1:
        groups = chosen_by.reshape(T, attrs.n_group, E // attrs.n_group)
        group_score = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
        _, best = lax.top_k(group_score, attrs.topk_group)
        is_open = jnp.any(best[:, :, None] == jnp.arange(attrs.n_group),
                          axis=1)
        chosen_by = jnp.where(is_open[:, :, None], groups,
                              -jnp.inf).reshape(T, E)
    _, ids = lax.top_k(chosen_by, attrs.k)
    return jnp.take_along_axis(scores, ids, axis=-1), ids


def _route_case(name, seed=0):
    """The router's choice alone for one launch of the cell, scores (rows,
    outputs) float32. Ling-3.0-flash's grouped router (sigmoid scores, a
    selection bias as its builder draws it): the whole selection ->
    (ids, weights) as `route` goes on to hand them out, normalised over
    the chosen and scaled, a sum that a fused selection could reorder.
    The two softmax routers KEEP their one `lax.top_k` (PERF.md section 6,
    PR 46): their case is the rounds alone against it, which is why."""
    from flexflow_tpu.ops import expert_share
    from flexflow_tpu.ops.attrs import ExpertShareAttrs

    rows, fields = ROUTERS[name]
    attrs = ExpertShareAttrs(hidden_dim=128, **fields)
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(rows, attrs.n_experts), jnp.float32)
    if not attrs.select_bias:
        return (lambda s: expert_share._top_k(s, attrs.k),
                (jax.nn.softmax(logits, axis=-1),),
                lambda s: lax.top_k(s, attrs.k))
    bias = jnp.asarray(rs.uniform(-0.003, 0.003, attrs.n_experts),
                       jnp.float32)

    def run(select):
        def fn(scores, bias):
            w, ids = select(attrs, scores, bias)
            return ids, w / jnp.sum(w, axis=-1, keepdims=True) * (
                attrs.routed_scale)
        return fn

    return (run(expert_share._select), (jax.nn.sigmoid(logits), bias),
            run(select_by_sort))


def route_cases():
    """name -> zero-argument builder of (fn, args, ref_fn), as
    `kernel_cases`; no case holds a Pallas kernel."""
    return {f"route_{name}": lambda name=name: _route_case(name)
            for name in ROUTERS}


def _loss_grads(attn, w):
    """(q, k, v) -> (loss, grads) of a fixed random projection of `attn`'s
    output: one function that runs the forward and the backward kernels."""
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    return lambda q, k, v: jax.value_and_grad(loss, (0, 1, 2))(q, k, v)


def _flash_case(d, seed=0):
    """flash fwd+bwd through the public entry (D=128 flat-lane kernels,
    D=64 padded head-major kernels), GQA, causal."""
    from flexflow_tpu.ops.jax_ops import _dot_product_attention
    from flexflow_tpu.ops.pallas import flash_attention

    B, S, h, hkv = 2, 1024, 16, 8
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, S, h, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, S, hkv, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, S, hkv, d), jnp.bfloat16)
    w = jnp.asarray(rs.randn(B, S, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    return (_loss_grads(lambda q, k, v: flash_attention(
                q, k, v, causal=True, scale=scale), w),
            (q, k, v),
            _loss_grads(lambda q, k, v: _dot_product_attention(
                q, k, v, True, scale), w))


def _ring_carry_case(seed=0):
    """One ring step (`_fwd_carry`) from empty statistics: acc / l is plain
    causal attention over the block."""
    from flexflow_tpu.ops.jax_ops import _dot_product_attention
    from flexflow_tpu.ops.pallas.flash_attention import (
        LANES,
        NEG_INF,
        _fwd_carry,
    )

    BH, S = 8, 512
    rs = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rs.randn(BH, S, D), jnp.bfloat16)
               for _ in range(3))
    scale = 1.0 / np.sqrt(D)

    def fn(q, k, v):
        m = jnp.full((BH, S, LANES), NEG_INF, jnp.float32)
        l = jnp.zeros((BH, S, LANES), jnp.float32)
        acc = jnp.zeros((BH, S, D), jnp.float32)
        m, l, acc = _fwd_carry(q, k, v, m, l, acc, True, scale, 512, 512,
                               False)
        return acc / l[:, :, 0:1]

    def ref(q, k, v):
        return _dot_product_attention(q[:, :, None], k[:, :, None],
                                      v[:, :, None], True,
                                      scale)[:, :, 0].astype(jnp.float32)

    return fn, (q, k, v), ref


def _ring_flash_case(n_shards, seed=0):
    """Ring flash fwd+bwd under shard_map over a `seq` axis of the
    attached devices."""
    from jax.sharding import Mesh, PartitionSpec as P

    from flexflow_tpu.ops.jax_ops import _dot_product_attention
    from flexflow_tpu.ops.pallas.ring_flash import ring_flash_attention
    from flexflow_tpu.parallel.compat import shard_map

    B, S, h, hkv = 2, 512 * n_shards, 8, 4
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, S, h, D), jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, S, hkv, D), jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, S, hkv, D), jnp.bfloat16)
    w = jnp.asarray(rs.randn(B, S, h, D), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("seq",))
    spec = P(None, "seq", None, None)

    def ring(q, k, v):
        return shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, axis_name="seq", n_shards=n_shards, causal=True,
                scale=scale),
            mesh, (spec, spec, spec), spec, check_vma=False)(q, k, v)

    return (_loss_grads(ring, w), (q, k, v),
            _loss_grads(lambda q, k, v: _dot_product_attention(
                q, k, v, True, scale), w))


def kernel_cases(n_devices: int = 1):
    """name -> zero-argument builder of (fn, args, ref_fn)."""
    cases = {
        "flash_fwd_bwd_d128": lambda: _flash_case(128),
        "flash_fwd_bwd_d64": lambda: _flash_case(64),
        "ring_carry_d128": _ring_carry_case,
    }
    if n_devices > 1:
        cases[f"ring_flash_x{n_devices}"] = (
            lambda: _ring_flash_case(n_devices))
    for qdt, pdt, P in RAGGED_POOLS:
        for kind, S in RAGGED_WINDOWS:
            cases[f"ragged_{kind}{S}_q{qdt}_kv{pdt}_p{P}"] = (
                lambda kind=kind, S=S, qdt=qdt, pdt=pdt, P=P:
                _ragged_case(kind, S, qdt, pdt, P))
    for kind in BENCH_LAUNCHES:
        cases[f"ragged_bench_{kind}"] = lambda kind=kind: _bench_case(kind)
    for dt, P in MLA_POOLS:
        for kind, S in MLA_WINDOWS:
            cases[f"mla_{kind}{S}_{dt}_p{P}"] = (
                lambda kind=kind, S=S, dt=dt, P=P: _mla_case(kind, S, dt, P))
    for kind in MLA_BENCH:
        cases[f"mla_bench_{kind}"] = lambda kind=kind: _mla_bench_case(kind)
    for tokens in (8, 256):
        cases[f"moe_grouped_t{tokens}"] = (
            lambda tokens=tokens: _moe_case(tokens))
    cases["mla_wide_chunk64"] = _mla_wide_case
    for kind in ("chunk256", "decode", "chunk512", "strongdecay"):
        cases[f"kda_{kind}"] = lambda kind=kind: _kda_case(kind)
    for kind in ("decode", "chunk512", "weakdecay", "strongdecay"):
        cases[f"ssd_{kind}"] = lambda kind=kind: _ssd_case(kind)
    return cases


def _rel_err(got, ref):
    """Largest per-leaf ||got - ref|| / ||ref||."""
    errs = []
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if not np.isfinite(g).all():
            return float("inf")
        errs.append(float(np.linalg.norm(g - r)
                          / max(np.linalg.norm(r), 1e-30)))
    return max(errs)


# float32 against float32 at the highest precision: reassociation only
KDA_TOL = 2e-5

# which device operations a timed case's kernel is, by the case's prefix
KERNEL_MARKS = {"ragged_": "ragged_paged_attention",
                "mla_": "mla_paged_attention", "moe_": "moe_grouped",
                "kda_": "kda_ragged_scan", "ssd_": "ssd_ragged_scan"}


def _kernel_device_us(fn, fargs, mark, per_call=1, calls=20):
    """Device microseconds a call of the case's kernel(s) alone: the
    events named `mark` on the device's `XLA Ops` line in a profiler
    trace of `calls` calls (`per_call` kernels a call). Without a `mark`,
    of the whole program: its events on the `XLA Modules` line."""
    import glob
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*fargs)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    ns = [ev.duration_ns
          for plane in data.planes if plane.name.startswith("/device:TPU:0")
          for line in plane.lines
          if line.name == ("XLA Ops" if mark else "XLA Modules")
          for ev in line.events
          if not mark or mark in ev.name.split(" = ", 1)[0]]
    return (sum(ns) / 1e3 / calls if len(ns) == calls * per_call
            else float("nan"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="", help="substring filter on names")
    ap.add_argument("--time", action="store_true",
                    help="print each ragged case's device microseconds")
    args = ap.parse_args(argv)
    dev = jax.devices()
    print(f"platform={dev[0].platform} device_kind={dev[0].device_kind} "
          f"count={len(dev)} jax={jax.__version__}", flush=True)
    if dev[0].platform != "tpu":
        print("no TPU attached: this check compiles with Mosaic",
              file=sys.stderr)
        return 2
    failed = 0
    for name, build in {**kernel_cases(len(dev)), **route_cases()}.items():
        if args.only not in name:
            continue
        try:
            fn, fargs, ref = build()
            jfn, jref = jax.jit(fn), jax.jit(ref)
            got = jax.block_until_ready(jfn(*fargs))
            want = jax.block_until_ready(jref(*fargs))
            err = _rel_err(got, want)
            # bf16 inputs, f32 accumulation on both sides; the scan is
            # float32 throughout; the router's choice is the same bits
            ok = (err == 0 if name.startswith("route_") else
                  err < (KDA_TOL if name.startswith(("kda_", "ssd_"))
                       else 2e-2))
            took = ""
            mark = next((m for p, m in KERNEL_MARKS.items()
                         if name.startswith(p)), None)
            if args.time and mark:
                us = _kernel_device_us(jfn, fargs, mark,
                                       per_call=2 if mark == "moe_grouped"
                                       else 1)
                took = f" kernel_us={us:.1f}"
                if name.startswith("ssd_"):
                    took += (" heads_a_step="
                             f"{_ssd_heads_a_step(name[len('ssd_'):])}")
            elif args.time and name.startswith("route_"):
                us, by_sort = (_kernel_device_us(f, fargs, None)
                               for f in (jfn, jref))
                took = f" program_us={us:.1f} by_sort_us={by_sort:.1f}"
            print(f"{'OK  ' if ok else 'FAIL'} {name} rel_err={err:.3e}"
                  f"{took}", flush=True)
        except Exception as e:  # report every case, then fail the run
            ok = False
            msg = " ".join(str(e).split())
            print(f"FAIL {name} {type(e).__name__}: {msg[:1500]}",
                  flush=True)
        failed += not ok
    print(f"{failed} case(s) failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
