"""Paged KV-cache + continuous batching (flexflow_tpu.paged).

Parity contract: the paged decode path must be TOKEN-IDENTICAL to the
dense GenerationServer / FFModel.generate on the same prompts (greedy),
and logits-identical at the decode-step level — the page indirection is
a memory layout, never a numerics change.
"""

import functools
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.paged.pool import PagePool


def _causal_lm(kv_heads=2, seed=7):
    """Tiny causal LM; kv_heads=2 is GQA (4 q heads), 4 is MHA."""
    lcfg = LlamaConfig(vocab_size=512, dim=64, layers=2, heads=4,
                      kv_heads=kv_heads, hidden=128, rope_theta=10000.0)
    ff = FFModel(FFConfig(batch_size=1, seed=seed))
    build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, lcfg


# ---------------------------------------------------------------------------
# page pool bookkeeping (host-side numpy)


def test_page_pool_alloc_free_accounting():
    pool = PagePool(num_pages=8, page_size=4, max_pages_per_seq=4)
    assert pool.capacity == 7 and pool.free_pages == 7
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert len(a) == 3 and len(b) == 2 and 0 not in a + b  # null reserved
    assert pool.free_pages == 2 and pool.pages_in_use == 5
    assert pool.alloc(3) is None  # never partial
    assert pool.free_pages == 2
    pool.free(a)
    assert pool.free_pages == 5
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2


def test_page_pool_refcount_and_lru_cache():
    """Refcounted content-addressed pages: lookup maps shared pages and
    bumps refs; free at ref 0 parks hashed pages on the LRU dead list
    (still hittable); fresh allocation reclaims the oldest dead page and
    drops its hash entry (a later lookup of that prefix misses)."""
    pool = PagePool(num_pages=6, page_size=4, max_pages_per_seq=4)
    toks = np.arange(8, dtype=np.int32)
    chain = pool.chain_hashes(toks)
    assert len(chain) == 2 and chain[0] != chain[1]
    # deterministic: same tokens -> same chain (content addressing)
    assert pool.chain_hashes(toks) == chain

    a = pool.alloc(2)
    pool.register_full(a[0], chain[0])
    pool.register_full(a[1], chain[1])
    # a second request sharing the prefix maps the SAME pages
    pages, cached, cow = pool.lookup(toks)
    assert pages == a and cached == 8 and cow is None
    assert pool.refcount(a[0]) == 2
    assert pool.pages_in_use == 2  # shared pages count once
    pool.free(a)                   # first owner releases
    assert pool.refcount(a[0]) == 1 and pool.pages_in_use == 2
    pool.free(pages)               # second owner releases -> dead-cached
    assert pool.pages_in_use == 0 and pool.cached_pages == 2
    # still a cache hit while dead
    pages2, cached2, _ = pool.lookup(toks)
    assert pages2 == a and cached2 == 8 and pool.cached_pages == 0
    pool.free(pages2)
    # pressure reclaims the OLDEST dead page and unregisters it
    grab = pool.alloc(5)
    assert grab is not None and pool.evictions >= 1
    p3, c3, _ = pool.lookup(toks)
    assert c3 < 8  # the evicted block no longer hits
    pool.free(p3)


def test_page_pool_partial_tail_cow_lookup():
    """A partially filled tail page registered under (parent hash, tail
    tokens) is served as a copy-on-write donor: lookup pins it and
    reports the matched tail rows; a diverging tail misses."""
    pool = PagePool(num_pages=6, page_size=4, max_pages_per_seq=4)
    toks = np.array([5, 6, 7, 8, 9, 10], np.int32)  # 1 full block + 2 tail
    chain = pool.chain_hashes(toks)
    pages = pool.alloc(2)
    pool.register_full(pages[0], chain[0])
    pool.register_partial(pages[1], chain[0], toks[4:])
    pool.free(pages)
    # identical prompt: full block + both tail rows, donor pinned
    got, cached, cow = pool.lookup(toks)
    assert got == [pages[0]] and cached == 6 and cow == pages[1]
    assert pool.refcount(cow) == 1
    pool.free(got + [cow])
    # diverging tail: only the common prefix of the tail matches
    div = np.array([5, 6, 7, 8, 9, 99], np.int32)
    got, cached, cow = pool.lookup(div)
    assert cached == 5 and cow == pages[1]
    pool.free(got + [cow])
    # diverging INSIDE the full block: nothing matches
    miss = np.array([5, 6, 0, 8, 9, 10], np.int32)
    got, cached, cow = pool.lookup(miss)
    assert got == [] and cached == 0 and cow is None


def test_page_pool_defrag_rewrites_hash_index():
    """Defrag compacts live AND dead-cached pages and rewrites the
    content-address index, so prefix hits survive the page moves."""
    pool = PagePool(num_pages=10, page_size=4, max_pages_per_seq=4)
    toks = np.arange(8, dtype=np.int32)
    chain = pool.chain_hashes(toks)
    scratch = pool.alloc(3)   # occupy low ids
    pages = pool.alloc(2)
    pool.register_full(pages[0], chain[0])
    pool.register_full(pages[1], chain[1])
    pool.free(scratch)                 # unregistered -> truly free
    pool.free(pages)                   # dead-but-cached
    perm, old_to_new = pool.defrag()
    assert sorted(perm.tolist()) == list(range(10))
    moved = [int(old_to_new[p]) for p in pages]
    assert moved == [1, 2]             # compacted to the low end
    got, cached, _ = pool.lookup(toks)
    assert got == moved and cached == 8
    pool.free(got)


def test_page_pool_defrag_compacts_and_remaps():
    pool = PagePool(num_pages=10, page_size=4, max_pages_per_seq=4)
    a = pool.alloc(2)
    b = pool.alloc(3)
    pool.free(a)  # fragment: b's pages no longer contiguous from 1
    perm, old_to_new = pool.defrag()
    # b's pages land on 1..3, every old page appears exactly once in perm
    assert sorted(old_to_new[p] for p in b) == [1, 2, 3]
    assert sorted(perm.tolist()) == list(range(10))
    assert old_to_new[0] == 0 and perm[0] == 0  # null page fixed
    # perm is consistent with old_to_new on allocated pages
    for p in b:
        assert perm[old_to_new[p]] == p
    assert pool.pages_in_use == 3 and pool.free_pages == 6
    # post-defrag allocations come from the compacted free set
    c = pool.alloc(6)
    assert c is not None and len(set(c) & {1, 2, 3}) == 0


# ---------------------------------------------------------------------------
# ragged kernel vs gather reference (interpret mode; the same validation
# pattern as test_pallas_flash) — MIXED batches: decode rows, prefill
# chunks, token trees and padded entries in ONE launch


def _ragged_anc(kind, S, n):
    """(S, S) window visibility of one entry with n live rows: a chain
    (decode, chunk) is lower-triangular, a tree its ancestor-or-self
    relation (root + two branches sharing it: a real non-causal mask),
    a padded entry sees nothing."""
    anc = np.zeros((S, S), bool)
    if kind == "tree":
        from flexflow_tpu.spec.tree import ancestor_masks

        parents = np.full((S,), -1, np.int32)
        parents[:n] = np.array([-1, 0, 1, 0, 3], np.int32)[:n]
        anc[:] = ancestor_masks(parents[None])[0]
    elif kind != "pad":
        anc[:n, :n] = np.tril(np.ones((n, n), bool))
    return anc


def _ragged_entry(kind, S, rs):
    """(pos, q_len, anc) for one batch entry of a window-S launch."""
    if kind == "pad":
        pos, n = 0, 0
    elif kind == "decode":
        pos, n = int(rs.randint(1, 28)), 1
    elif kind == "chunk":
        n = int(rs.randint(2, S + 1))
        pos = int(rs.randint(0, 24))
    else:
        pos, n = int(rs.randint(0, 24)), min(S, 5)
    return pos, n, _ragged_anc(kind, S, n)


# Launches that cross what the blocked walk adds (name -> entries
# (kind, pos, q_len) in units the test resolves against the kernel's OWN
# derived block: K = keys a block, L = rows the table maps, E = where the
# second block ends, T = where the table's last block starts). The wide
# geometry is page 64, two kv heads x 128, a table 40 pages wide, so a
# float32 pool walks blocks of 16 pages (16, 16, 8: the table is no
# multiple of the block) and a bfloat16 one blocks of 32 (32, 8).
_WIDE_CASES = {
    # the horizon pos + q_len - 1 on the first / a middle / the last page
    # of the second block
    "horizon_pages": lambda K, L, P, E, T: [
        ("chunk", K + 10, 6), ("chunk", (K + E) // 2 + 5, 6),
        ("chunk", E - 20, 6), ("decode", E - 1, 1)],
    # a window straddling two blocks (chain and tree), and windows that
    # end / begin exactly on the boundary
    "straddle": lambda K, L, P, E, T: [
        ("chunk", K - 3, 6), ("tree", K - 2, 5), ("decode", K - 1, 1),
        ("decode", K, 1), ("chunk", K - 6, 6)],
    # pos = 0, slots with fewer live pages than a block, padded entries
    # between live ones
    "pos0_short_padded": lambda K, L, P, E, T: [
        ("chunk", 0, 6), ("pad", 0, 0), ("decode", 100, 1), ("pad", 0, 0),
        ("pad", 0, 0), ("tree", T + 5, 5), ("decode", 0, 1),
        ("pad", 0, 0)],
    # the table's last, partial block: a horizon inside it and the
    # window on the table's very last rows
    "table_end": lambda K, L, P, E, T: [
        ("chunk", T + 300, 4), ("chunk", L - 6, 6),
        ("decode", L - 1, 1), ("tree", L - P - 2, 5)],
}


@pytest.mark.parametrize("H,Hkv,S,mix,dtype", [
    (8, 2, 1, ["decode", "decode", "decode"], None),
    (8, 2, 4, ["chunk", "chunk"], None),
    (8, 2, 4, ["decode", "chunk", "pad"], None),
    (8, 2, 6, ["decode", "tree"], None),
    (8, 2, 6, ["decode", "chunk", "tree", "pad"], None),
    (4, 4, 6, ["decode", "chunk", "tree", "pad"], None),  # MHA rep=1
    (4, 2, 6, "horizon_pages", "float32"),
    (4, 2, 6, "straddle", "float32"),
    (8, 2, 6, "straddle", "float32"),                     # rep=4 fold
    (2, 2, 6, "straddle", "float32"),                     # MHA rep=1
    (4, 2, 6, "pos0_short_padded", "float32"),
    (4, 2, 6, "table_end", "float32"),
    (4, 2, 6, "horizon_pages", "bfloat16"),   # bf16 pool under bf16 q
    (4, 2, 6, "straddle", "bfloat16"),
    (4, 2, 6, "table_end", "bfloat16"),
])
def test_ragged_kernel_matches_gather_reference(H, Hkv, S, mix, dtype):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.paged.attention import (
        ragged_block_pages,
        ragged_flash_attention,
        ragged_gather_attention,
    )

    if dtype is None:
        # the narrow geometry: every slot lives inside one block
        B, D, P, N, MAXP = len(mix), 32, 8, 24, 4
        dtype, tol = "float32", 2e-5
        rs = np.random.RandomState(1000 * S + len(mix))
        entries = [_ragged_entry(k, S, rs) for k in mix]
    else:
        D, P, MAXP = 128, 64, 40
        ppb = ragged_block_pages(P, MAXP, Hkv * D, dtype, (H // Hkv) * S)
        # wider than one block, and no multiple of it
        assert ppb < MAXP and MAXP % ppb
        K, L = ppb * P, MAXP * P
        kinds = _WIDE_CASES[mix](K, L, P, min(2 * K, L), (L - 1) // K * K)
        B, N = len(kinds), len(kinds) * MAXP + 1
        # bfloat16 on both sides rounds p and the output to 8 bits
        tol = 2e-5 if dtype == "float32" else 2e-2
        rs = np.random.RandomState(len(mix))
        entries = [(pos, n, _ragged_anc(k, S, n)) for k, pos, n in kinds]
        mix = [k for k, _, _ in kinds]
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    kc = jax.random.normal(ks[1], (N, P, Hkv * D), dtype)
    vc = jax.random.normal(ks[2], (N, P, Hkv * D), dtype)
    perm = rs.permutation(N - 1)[:B * MAXP] + 1  # distinct non-null pages
    pt = jnp.asarray(perm.reshape(B, MAXP).astype(np.int32))
    pos = jnp.asarray(np.array([e[0] for e in entries], np.int32))
    q_lens = jnp.asarray(np.array([e[1] for e in entries], np.int32))
    anc = jnp.asarray(np.stack([e[2] for e in entries]))
    scale = 1.0 / np.sqrt(D)
    ref = np.asarray(ragged_gather_attention(q, kc, vc, pt, pos, q_lens,
                                             anc, scale=scale), np.float32)
    got = np.asarray(ragged_flash_attention(q, kc, vc, pt, pos, q_lens,
                                            anc, scale=scale,
                                            interpret=True), np.float32)
    for b, kind in enumerate(mix):
        n = int(q_lens[b])
        np.testing.assert_allclose(got[b, :n], ref[b, :n], atol=tol,
                                   rtol=tol, err_msg=f"entry {b} {kind}")
        # the kernel's contract: rows at or past q_len are exact zeros
        # (the gather fallback's garbage rows differ — both discarded)
        assert not got[b, n:].any(), f"entry {b} {kind} padded tail"


# ---------------------------------------------------------------------------
# runs: consecutive entries that continue one another over the same pages
# share ONE walk (paged/attention.py `ragged_runs`)

# name -> (entries, window, pool, expected run lengths). An entry is
# (kind, slot, pos, q_len): entries of one slot share a table row, "share"
# slots share slot 0's pages below the page their first entry writes,
# "share+" slots that page too.
# Geometry: page 64, 8 q / 2 kv heads x 128 (32 folded rows an entry), a
# table 40 pages wide: blocks of 1024 keys (float32) and row tiles of 4
# entries, so a run of 16 pieces is four tiles over two blocks.
_PIECES = 8


def _pieces(slot, start, n, last=_PIECES):
    return [("chunk", slot, start + _PIECES * i,
             last if i == n - 1 else _PIECES) for i in range(n)]


_RUN_CASES = {
    # 16 pieces of one slot crossing a block (1024) and a page boundary
    "run16_full": (_pieces(0, 950, 16), None, "float32", [16] + [0] * 15),
    "run16_full_bf16": (_pieces(0, 950, 16), None, "bfloat16",
                        [16] + [0] * 15),
    # a sliding layer, window shorter and longer than the prefix
    "run16_window_short": (_pieces(0, 950, 16), 200, "float32",
                           [16] + [0] * 15),
    "run16_window_long": (_pieces(0, 950, 16), 4000, "float32",
                          [16] + [0] * 15),
    # a window of about a block: blocks before, at and behind the windows
    "run16_window_block": (_pieces(0, 2100, 16), 1030, "float32",
                           [16] + [0] * 15),
    "run_riders": (_pieces(0, 1000, 5) + [("decode", 1, 70, 1),
                                          ("decode", 2, 1500, 1),
                                          ("decode", 3, 0, 1)],
                   None, "float32", [5, 0, 0, 0, 0, 1, 1, 1]),
    "run_riders_window": (_pieces(0, 1000, 5) + [("decode", 1, 70, 1),
                                                 ("decode", 2, 1500, 1)],
                          300, "float32", [5, 0, 0, 0, 0, 1, 1]),
    "two_runs": (_pieces(0, 500, 4) + _pieces(1, 1010, 10), None,
                 "float32", [4, 0, 0, 0, 10] + [0] * 9),
    "broken_by_pad": (_pieces(0, 300, 3) + [("pad", 0, 0, 0)]
                      + _pieces(0, 324, 3), None, "float32",
                      [3, 0, 0, 1, 3, 0, 0]),
    "short_last": (_pieces(0, 1016, 4, last=3), None, "float32",
                   [4, 0, 0, 0]),
    "short_last_window": (_pieces(0, 1016, 4, last=3), 100, "float32",
                          [4, 0, 0, 0]),
    # equal tables, positions that do not continue: no run across the gap
    "not_contiguous": ([("chunk", 0, 100, 8), ("chunk", 0, 108, 8),
                        ("chunk", 0, 200, 8), ("chunk", 0, 208, 8)],
                       None, "float32", [2, 0, 2, 0]),
    # two slots over one prefix, at contiguous positions: the page being
    # written differs, so neither rides the other's walk
    "prefix_shared": ([("chunk", 0, 120, 8), ("share", 1, 128, 8),
                       ("share", 1, 136, 8)], None, "float32", [1, 2, 0]),
    # three entries at contiguous positions, the second slot's row equal
    # to the first's up to the second entry's horizon and different at the
    # page the third reaches: compared a pair at a time up to the later
    # entry's horizon all three would be one run, walked over the FIRST
    # entry's row, and the third would read another slot's page
    "rows_differ_later": ([("chunk", 0, 176, 8), ("share+", 1, 184, 8),
                           ("share+", 1, 192, 8)], None, "float32",
                          [1, 2, 0]),
    "tree_between": ([("chunk", 0, 1000, 8), ("tree", 1, 1008, 5),
                      ("chunk", 0, 1008, 8), ("chunk", 0, 1016, 8)],
                     None, "float32", [1, 1, 2, 0]),
    "int8_run": (_pieces(0, 1000, 4) + [("decode", 1, 40, 1)], None,
                 "int8", [4, 0, 0, 0, 1]),
}


@pytest.mark.parametrize("case", sorted(_RUN_CASES))
def test_ragged_kernel_runs_share_one_walk(case):
    """The kernel (interpreted) against the gather reference over
    launches whose entries form runs, and `ragged_runs` itself: which
    entries share a walk is read from (table, pos, q_lens, anc) alone."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.paged.attention import (
        ragged_block_pages,
        ragged_flash_attention,
        ragged_gather_attention,
        ragged_run_tile,
        ragged_runs,
    )
    from flexflow_tpu.paged.quant import quantized_append

    entries, window, pool, want_runs = _RUN_CASES[case]
    H, Hkv, D, P, MAXP, S = 8, 2, 128, 64, 40, _PIECES
    qdt = "float32" if pool == "int8" else pool
    ppb = ragged_block_pages(P, MAXP, Hkv * D, pool, (H // Hkv) * S)
    B = len(entries)
    if pool == "float32":
        assert ppb * P == 1024
        assert ragged_run_tile(S, (H // Hkv) * S, ppb * P, 16) == 4
    slots = sorted({s for _, s, _, _ in entries})
    N = len(slots) * MAXP + 1
    rs = np.random.RandomState(len(case))
    perm = (rs.permutation(N - 1) + 1).reshape(len(slots), MAXP)
    tables = {s: perm[i].astype(np.int32) for i, s in enumerate(slots)}
    for kind, s, p, _ in entries:
        if kind in ("share", "share+"):   # slot 0's pages below the one
            first = min(e[2] for e in entries if e[1] == s)     # written
            upto = first // P + (kind == "share+")
            tables[s][:upto] = tables[0][:upto]
    pt = jnp.asarray(np.stack([tables[s] for _, s, _, _ in entries]))
    pos = jnp.asarray(np.array([e[2] for e in entries], np.int32))
    q_lens = jnp.asarray(np.array([e[3] for e in entries], np.int32))
    anc = jnp.asarray(np.stack([
        _ragged_anc(k, S, n)
        if k in ("tree", "pad") else np.tril(np.ones((S, S), bool))
        for k, _, _, n in entries]))
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), qdt)
    sc = {}
    if pool == "int8":
        rows = jax.random.normal(ks[1], (2, N, P, Hkv, D), jnp.float32)
        page = jnp.broadcast_to(jnp.arange(N)[:, None], (N, P))
        off = jnp.broadcast_to(jnp.arange(P)[None], (N, P))
        (kc, k_sc), (vc, v_sc) = (
            quantized_append(jnp.zeros((N, P, Hkv * D), jnp.int8),
                             jnp.zeros((N, Hkv), jnp.float32), x, page,
                             off, jnp.ones((N, P), bool)) for x in rows)
        sc = {"k_scales": k_sc, "v_scales": v_sc}
    else:
        kc = jax.random.normal(ks[1], (N, P, Hkv * D), pool)
        vc = jax.random.normal(ks[2], (N, P, Hkv * D), pool)

    run_len, horizon = ragged_runs(pt, pos, q_lens, anc)
    assert list(np.asarray(run_len)) == want_runs
    ends = np.asarray(pos + q_lens)
    for b, n in enumerate(want_runs):
        live = n and int(q_lens[b])
        assert int(horizon[b]) == (ends[b + n - 1] if live else 0)

    kw = dict(scale=1.0 / np.sqrt(D), window=window, **sc)
    ref = np.asarray(ragged_gather_attention(q, kc, vc, pt, pos, q_lens,
                                             anc, **kw), np.float32)
    got = np.asarray(ragged_flash_attention(q, kc, vc, pt, pos, q_lens,
                                            anc, interpret=True, **kw),
                     np.float32)
    tol = 2e-2 if pool == "bfloat16" else 2e-5
    for b, (kind, _, _, n) in enumerate(entries):
        np.testing.assert_allclose(got[b, :n], ref[b, :n], atol=tol,
                                   rtol=tol, err_msg=f"entry {b} {kind}")
        assert not got[b, n:].any(), f"entry {b} {kind} padded tail"
    if case == "tree_between":
        # a tree is a run of its own whatever rides beside it: the same
        # bits as the launch that holds nothing else
        alone = ragged_flash_attention(
            q[1:2], kc, vc, pt[1:2], pos[1:2], q_lens[1:2], anc[1:2],
            interpret=True, **kw)
        np.testing.assert_array_equal(got[1], np.asarray(alone[0],
                                                         np.float32))


@pytest.mark.parametrize("case", ["chunk_riders", "trees", "pad"])
def test_host_walks_equal_device_runs(case, walks_against_runs):
    """What rides a walk is decided twice: on the device from the arrays
    of the launch (`ragged_runs`, what the kernel does) and on the host
    from the items (`_walks`, what `kv_pages`, `kv_blocks` and
    `walk_shared_share` count). On the scheduler's own launches the two
    agree: a chunk's pieces with decode rows riding behind them and a
    narrow last launch, a speculative server's trees beside a chunk, and
    a run broken by an item without rows. (A graph with window layers
    and its two classes of tables: tests/test_mellum2.py.)"""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(3)
    kw = {}
    if case == "trees":
        from flexflow_tpu.spec import SpecConfig

        kw["speculate"] = SpecConfig(width=2, depth=2)
    srv = ff.serve_generation(slots=3, max_len=64, paged=True, page_size=8,
                              prefill_chunk=24, **kw)

    def prompt(n):
        return rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)

    def drive():
        first = srv.submit(prompt(5), max_new_tokens=14)
        later = [srv.submit(prompt(n), max_new_tokens=3) for n in (43, 27)]
        for f in [first] + later:
            f.result(timeout=300)

    try:
        if case != "pad":
            seen = walks_against_runs(srv, drive)
        else:
            # three pieces, an item without rows, three pieces that go on
            # where the first three ended: two walks of three
            srv.generate(prompt(50), max_new_tokens=1)
            pos = np.array([0, 8, 16, 0, 24, 32, 40], np.int32)
            qls = np.array([8, 8, 8, 0, 8, 8, 8], np.int32)
            slot_idx = np.zeros((7,), np.int32)
            items = [(0, int(p), [1] * int(n), None, None)
                     for p, n in zip(pos, qls)]
            tbl = np.asarray(srv._tables_device())[slot_idx]
            anc = np.tile(np.tril(np.ones((8, 8), bool)), (7, 1, 1))

            def drive():
                srv._walks(items, 8, slot_idx, pos, qls, True)
                srv._step(None, None, None, tbl, pos, qls, None, anc, None)

            step = srv._step
            srv._step = lambda *a, **k: None
            try:
                seen = walks_against_runs(srv, drive)
            finally:
                srv._step = step
            assert list(seen[0][0]) == [False, True, True, False, False,
                                        True, True]
    finally:
        srv.stop()
    rode = [r for r, *_ in seen]
    assert any(r.sum() >= 2 for r in rode)          # a chunk's pieces
    if case == "chunk_riders":
        # decode rows behind the pieces, and a launch narrower than a piece
        assert any(r.sum() and not r[-1] and q[-1] == 1
                   for r, _t, _p, q, _a in seen)
        assert any(a.shape[1] < 8 for *_, a in seen)
    if case == "trees":
        assert any((a != np.tril(np.ones(a.shape[1:], bool))).any()
                   for *_, a in seen)


# the widths of the two serving configurations the benchmark runs
# (H, D, page, table width, pool lanes), bfloat16 pools and queries
_MISTRAL_7B = (32, 128, 64, 64, 1024, "bfloat16", "bfloat16")
_MELLUM2 = (32, 128, 64, 516, 512, "bfloat16", "bfloat16")


def test_ragged_launch_vmem_is_held_to_the_core(monkeypatch, caplog):
    """The launch's queries, output and statistics are whole in VMEM, so
    what a launch keeps there grows with its entries: the cells' largest
    launches and the widest a speculative catalogue packs fit and lower
    for the TPU; a launch that does not fit is refused by name in the
    wrapper and takes the gather path through the one entry point,
    instead of failing in Mosaic."""
    import logging

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.analysis.shapecheck import enumerate_catalog
    from flexflow_tpu.paged import attention as pa

    assert pa.ragged_launch_fits(15, 8, *_MISTRAL_7B)
    assert pa.ragged_launch_fits(19, 8, *_MELLUM2)
    shapes = enumerate_catalog(slots=8, max_len=4096, spec_max_nodes=64)[
        "entries"]["ragged_step"]["shapes"]
    B, S = max(shapes, key=lambda bw: bw[0] * bw[1])
    assert (B, S) == (8, 64) and pa.ragged_launch_fits(B, S, *_MISTRAL_7B)
    assert not pa.ragged_launch_fits(24, 64, *_MISTRAL_7B)

    H, D, P, width, lanes, pdt, qdt = _MISTRAL_7B

    def launch(B, S):
        args = (jnp.zeros((B, S, H, D), qdt), jnp.zeros((B, S, 8, D), qdt),
                jnp.zeros((B, S, 8, D), qdt), jnp.zeros((80, P, lanes), pdt),
                jnp.zeros((80, P, lanes), pdt),
                jnp.zeros((B, width), jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.full((B,), S, jnp.int32), jnp.zeros((B, S), jnp.int32),
                jnp.ones((B, S, S), bool))
        return functools.partial(pa.ragged_paged_attention,
                                 scale=0.1), args

    fn, args = launch(B, S)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()
    assert "tpu_custom_call" in text
    fn, args = launch(24, 64)
    pa.reset_rejection_log()
    with caplog.at_level(logging.WARNING, logger=pa.__name__):
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *args).mlir_module()
    assert "tpu_custom_call" not in text
    assert "24 entries x 64 rows" in caplog.text
    with pytest.raises(ValueError, match="MiB in VMEM"):
        jax.eval_shape(functools.partial(
            pa.ragged_flash_attention, scale=0.1), args[0], *args[3:8],
            args[9])


# ---------------------------------------------------------------------------
# decode-step logits parity (executor level): dense cache vs page pool


def test_paged_decode_logits_match_dense():
    import jax.numpy as jnp

    ff, lcfg = _causal_lm()
    ex = ff.executor
    tr, ntr = ff._params
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, lcfg.vocab_size, (1, 5)).astype(np.int32)
    P, MAXP = 4, 4  # max_len 16

    dense = ex.init_kv_cache(1, 16)
    step = ex.decode_fn()
    probs, dense = step(tr, ntr, dense, 0, jnp.asarray(prompt))

    pools = ex.init_paged_kv_cache(9, P)
    # scatter the dense prefill rows into pages [1, 2] (5 tokens -> 2 pages)
    ids = jnp.asarray(np.array([1, 2], np.int32))
    for key in pools:
        pools[key] = {
            n: pools[key][n].at[ids].set(
                dense[key][n][0].reshape(MAXP, P, -1)[:2])
            for n in ("k", "v")
        }
    tables = jnp.asarray(np.array([[1, 2, 3, 0]], np.int32))
    pstep = ex.ragged_step_fn()
    # the (slots, 1) decode shape of the one paged step: one live row a
    # slot at depth 0, visible to itself; the pools are donated, so each
    # call's output pools are the next call's input
    q_lens = jnp.ones((1,), jnp.int32)
    depths = jnp.zeros((1, 1), jnp.int32)
    anc = jnp.ones((1, 1, 1), jnp.bool_)

    tok = jnp.argmax(probs[:, 4, :], axis=-1).astype(jnp.int32)
    for pos in range(5, 8):  # crosses no page boundary until pos 8
        probs_d, dense = step(tr, ntr, dense, pos, tok[:, None])
        probs_p, pools = pstep(tr, ntr, pools, tables,
                               jnp.asarray(np.array([pos], np.int32)),
                               q_lens, depths, anc, tok[:, None])
        np.testing.assert_allclose(np.asarray(probs_p[:, -1]),
                                   np.asarray(probs_d[:, -1]),
                                   atol=1e-5, rtol=1e-5)
        tok = jnp.argmax(probs_d[:, -1, :], axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# served-token parity vs dense generate()


@pytest.mark.parametrize("kv_heads", [2, 4])  # GQA and MHA
def test_paged_server_matches_dense_generate(kv_heads):
    """Greedy continuous batching through the page pool emits EXACTLY the
    tokens one-at-a-time generate() emits — with prompts SPANNING page
    boundaries (page_size=4, prompts up to 8 tokens) and staggered
    lengths, so page-table indirection, prefill scatter, growth, and
    stale-page masking all have to be right."""
    ff, lcfg = _causal_lm(kv_heads=kv_heads)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 8, 5, 2, 6)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                 page_size=4)
    try:
        futs = [server.submit(p, max_new_tokens=5) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert server.requests_served == len(prompts)
    assert server.decode_steps < 25  # continuous, not serial


def test_paged_temperature_sampling_matches_dense_server():
    """Dense and paged servers share ONE sampling implementation and rng
    discipline: with the same seed and a single in-flight request, their
    sampled (temperature>0) streams are identical."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(3)
    p = rs.randint(0, lcfg.vocab_size, (4,)).astype(np.int32)
    dense = ff.serve_generation(slots=2, max_len=16, seed=5)
    try:
        want = dense.generate(p, max_new_tokens=6, temperature=0.9)
    finally:
        dense.stop()
    paged = ff.serve_generation(slots=2, max_len=16, seed=5, paged=True,
                                page_size=4)
    try:
        got = paged.generate(p, max_new_tokens=6, temperature=0.9)
    finally:
        paged.stop()
    np.testing.assert_array_equal(want, got)


# ---------------------------------------------------------------------------
# scheduler policy: admission by page budget, exhaustion, preemption


def test_page_pool_exhaustion_queues():
    """A pool that only fits ONE request serializes: later submissions
    queue for pages (never fail, never corrupt), and every request still
    matches dense generate()."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, lcfg.vocab_size, (5,)).astype(np.int32)
               for _ in range(3)]
    want = [ff.generate(p[None, :], max_new_tokens=3)[0] for p in prompts]
    # capacity 2 pages (8 tokens); each request needs 2 pages at its peak
    server = ff.serve_generation(slots=4, max_len=16, paged=True,
                                 page_size=4, num_pages=3)
    try:
        futs = [server.submit(p, max_new_tokens=3) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    m = server.metrics()
    assert m["requests_served"] == 3
    assert m["peak_active"] == 1  # pages, not slots, bounded concurrency
    assert m["pages_in_use"] == 0  # everything returned to the pool


def test_preemption_requeues_and_stays_correct():
    """Page pressure preempts the youngest request; it requeues with its
    prompt + generated prefix and still produces the dense-identical
    greedy continuation."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 6, 4, 7)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    # 2 slots want up to 2*ceil(13/4)=8 pages at their peak; pool holds 5
    server = ff.serve_generation(slots=2, max_len=16, paged=True,
                                 page_size=4, num_pages=6)
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    m = server.metrics()
    assert m["preemptions"] > 0, "pool pressure never preempted"
    assert m["requests_served"] == 4
    # per-request metrics: the preempted request recorded its requeue
    assert sum(r["preemptions"] for r in m["requests"]) == m["preemptions"]
    for r in m["requests"]:
        assert r["queue_time_s"] >= 0 and r["decode_tokens"] == 6
        assert r["pages_held_peak"] >= 1


def test_paged_admits_more_concurrency_than_dense_layout():
    """THE paging win (acceptance criterion): with the pool sized to the
    HBM of only TWO dense max_len slots, short requests still run FOUR
    abreast — concurrency beyond what the dense slots x max_len layout
    could hold — and everything matches dense greedy output."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, lcfg.vocab_size, (3,)).astype(np.int32)
               for _ in range(6)]
    want = [ff.generate(p[None, :], max_new_tokens=8)[0] for p in prompts]
    max_len, page_size, num_pages = 16, 4, 9
    # dense-equivalent capacity of this pool: (9-1)*4 = 32 cached tokens
    # = 2 slots of max_len 16
    dense_equiv_slots = (num_pages - 1) * page_size // max_len
    assert dense_equiv_slots == 2
    server = ff.serve_generation(slots=4, max_len=max_len, paged=True,
                                 page_size=page_size, num_pages=num_pages)
    try:
        futs = [server.submit(p, max_new_tokens=8) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    m = server.metrics()
    assert m["requests_served"] == 6
    assert m["peak_active"] > dense_equiv_slots, (
        f"paged pool admitted only {m['peak_active']} concurrent requests; "
        f"a dense layout with the same HBM holds {dense_equiv_slots}")


def test_defrag_compacts_pool_mid_stream():
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 6)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    server = ff.serve_generation(slots=2, max_len=16, paged=True,
                                 page_size=4)
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        server.request_defrag()
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert server.defrags >= 1


def test_concurrent_submit_under_page_pressure():
    """Multi-threaded submit() racing the scheduler loop while the pool
    is tight enough to preempt: every caller gets the dense-identical
    greedy answer, the metrics are consistent, and every page returns to
    the pool. (The submit path is lock-guarded against stop(); this
    exercises it against admission/preemption churn.)"""
    import threading

    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 6, 4, 7, 3, 5, 6, 4)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    server = ff.serve_generation(slots=3, max_len=16, paged=True,
                                 page_size=4, num_pages=7)
    got = [None] * len(prompts)
    errs = []

    def worker(idxs):
        try:
            for i in idxs:
                fut = server.submit(prompts[i], max_new_tokens=5)
                got[i] = fut.result(timeout=120)
        except Exception as e:  # surfaced on the main thread below
            errs.append(e)

    try:
        threads = [threading.Thread(target=worker,
                                    args=([i, i + 4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=150)
        stuck = [t for t in threads if t.is_alive()]
        assert not stuck, f"{len(stuck)} worker threads hung (scheduler " \
                          "deadlock?)"
    finally:
        server.stop()
    assert not errs, errs
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    m = server.metrics()
    assert m["requests_served"] == len(prompts)
    assert m["pages_in_use"] == 0
    assert len(m["requests"]) == len(prompts)


# ---------------------------------------------------------------------------
# prefix caching + chunked prefill (ISSUE 5 tentpole)


def test_shared_prefix_token_identity_and_hit_rate():
    """≥3 concurrent requests sharing a system-prompt prefix emit the
    dense-identical greedy tokens with the prefix cache ON and OFF, and
    with it on, the second and later requests serve ≥50% of their
    prompt rows from shared pages (the acceptance criterion)."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(11)
    sys_prompt = rs.randint(0, lcfg.vocab_size, (8,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rs.randint(0, lcfg.vocab_size, (3,))
                               .astype(np.int32)])
               for _ in range(4)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    for cache in (True, False):
        server = ff.serve_generation(slots=4, max_len=32, paged=True,
                                     page_size=4, prefix_cache=cache)
        try:
            # first request warms the shared blocks (registration happens
            # as its chunks complete, so same-tick admissions can't hit)
            first = server.submit(prompts[0], max_new_tokens=6)
            first.result(timeout=120)
            futs = [server.submit(p, max_new_tokens=6)
                    for p in prompts[1:]]
            got = [np.asarray(first.result())] + \
                  [f.result(timeout=120) for f in futs]
            m = server.metrics()
        finally:
            server.stop()
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        pc = m["prefix_cache"]
        if cache:
            # the 8-token system prompt is 2 full pages: every later
            # request serves >= 8 of its 11 prompt rows from the cache
            later = [r for r in m["requests"]
                     if r["cached_prefill_tokens"] > 0]
            assert len(later) >= 3, m["requests"]
            for r in later:
                frac = r["cached_prefill_tokens"] / (
                    r["cached_prefill_tokens"] + r["prefill_tokens"])
                assert frac >= 0.5, r
            assert pc["hit_tokens"] >= 3 * 8
        else:
            assert not pc["enabled"] and pc["hit_tokens"] == 0
            assert all(r["cached_prefill_tokens"] == 0
                       for r in m["requests"])


def test_prefix_cache_cow_divergence_after_shared_prefix():
    """Copy-on-write on the partially filled tail page: a request whose
    prompt extends a cached prompt past a mid-page boundary clones the
    donor page before writing its own rows — both the extended request
    and a fresh re-run of the ORIGINAL prompt stay dense-identical, and
    the tail rows count as cache hits."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(12)
    base = rs.randint(0, lcfg.vocab_size, (6,)).astype(np.int32)  # 1.5 pages
    ext = np.concatenate([base, rs.randint(0, lcfg.vocab_size, (3,))
                          .astype(np.int32)])
    want_base = ff.generate(base[None, :], max_new_tokens=5)[0]
    want_ext = ff.generate(ext[None, :], max_new_tokens=5)[0]
    server = ff.serve_generation(slots=2, max_len=32, paged=True,
                                 page_size=4)
    try:
        got0 = server.generate(base, max_new_tokens=5)   # donor
        got1 = server.generate(ext, max_new_tokens=5)    # COW + diverge
        got2 = server.generate(base, max_new_tokens=5)   # donor rows intact
        m = server.metrics()
    finally:
        server.stop()
    np.testing.assert_array_equal(want_base, got0)
    np.testing.assert_array_equal(want_ext, got1)
    np.testing.assert_array_equal(want_base, got2)
    reqs = m["requests"]
    # the extension hit the full page AND the 2-row tail (6 of 9 rows);
    # the re-run hit everything but the recomputed last row
    assert reqs[1]["cached_prefill_tokens"] >= 6, reqs[1]
    assert reqs[2]["cached_prefill_tokens"] >= 5, reqs[2]


def test_preempted_resume_reattaches_cached_pages():
    """Preemption + prefix cache: the victim's pages stay content-
    addressed on the LRU dead list, so its resume re-attaches them and
    recomputes only the non-cached suffix (asserted via the per-request
    cached/computed prefill counters), with dense-identical output."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, lcfg.vocab_size, (5,)).astype(np.int32)
               for _ in range(2)]
    want = [ff.generate(p[None, :], max_new_tokens=8)[0] for p in prompts]
    # capacity 5 pages; both requests peak at 3 pages (12 written rows)
    # -> one preemption is forced, the victim resumes after the winner
    # finishes and finds its own blocks still content-addressed
    server = ff.serve_generation(slots=2, max_len=16, paged=True,
                                 page_size=4, num_pages=6)
    try:
        futs = [server.submit(p, max_new_tokens=8) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        m = server.metrics()
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert m["preemptions"] > 0
    preempted = [r for r in m["requests"] if r["preemptions"] > 0]
    assert preempted, m["requests"]
    for r in preempted:
        # at least one page of its own prior work re-attached on resume
        assert r["cached_prefill_tokens"] >= 4, r
        # computed rows stay below the full per-admission recompute the
        # monolithic prefill would have paid (5 prompt rows + the
        # re-prefilled generated prefix on every resume)
        assert r["prefill_tokens"] < (r["preemptions"] + 1) * 5 + \
            r["decode_tokens"], r


def test_refcount_eviction_stress_under_page_pressure():
    """Shared-prefix requests churning through a tight pool (preemption,
    LRU eviction, COW, repeated resume): outputs stay dense-identical,
    every page returns to the pool, and the refcount invariants hold."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(14)
    sys_prompt = rs.randint(0, lcfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rs.randint(0, lcfg.vocab_size, (n,))
                               .astype(np.int32)])
               for n in (2, 3, 4, 2, 3, 4)]
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    server = ff.serve_generation(slots=3, max_len=16, paged=True,
                                 page_size=4, num_pages=8)
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=180) for f in futs]
        m = server.metrics()
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert m["requests_served"] == len(prompts)
    assert m["pages_in_use"] == 0  # every reference released
    pool = server.pool
    assert pool._refs == {}, pool._refs
    assert len(pool._free) + len(pool._lru) == pool.capacity


def test_defrag_with_shared_pages_mid_stream():
    """Defrag while two live requests SHARE prefix pages: the page moves
    rewrite both owners' tables and the hash index, and output stays
    dense-identical."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(15)
    sys_prompt = rs.randint(0, lcfg.vocab_size, (8,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rs.randint(0, lcfg.vocab_size, (2,))
                               .astype(np.int32)])
               for _ in range(3)]
    want = [ff.generate(p[None, :], max_new_tokens=8)[0] for p in prompts]
    server = ff.serve_generation(slots=3, max_len=32, paged=True,
                                 page_size=4)
    try:
        first = server.submit(prompts[0], max_new_tokens=8)
        first.result(timeout=120)       # warm the shared blocks
        futs = [server.submit(p, max_new_tokens=8) for p in prompts[1:]]
        server.request_defrag()         # compact under live sharing
        got = [np.asarray(first.result())] + \
              [f.result(timeout=120) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert server.defrags >= 1
    m = server.metrics()
    assert m["prefix_cache"]["hit_tokens"] >= 2 * 8


def test_chunked_prefill_does_not_stall_decodes():
    """A prompt longer than the chunk budget admits and prefills chunk by
    chunk INSIDE the decode loop: the already-running request keeps
    decoding between the chunks (>= 2 overlapped decode ticks recorded),
    and both outputs are dense-identical (scheduler acceptance
    criterion)."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(16)
    short = rs.randint(0, lcfg.vocab_size, (4,)).astype(np.int32)
    long = rs.randint(0, lcfg.vocab_size, (24,)).astype(np.int32)
    want_short = ff.generate(short[None, :], max_new_tokens=20)[0]
    want_long = ff.generate(long[None, :], max_new_tokens=4)[0]
    server = ff.serve_generation(slots=2, max_len=48, paged=True,
                                 page_size=4, prefill_chunk=4)
    try:
        f_short = server.submit(short, max_new_tokens=20)
        # wait until the short request is live and decoding
        deadline = time.monotonic() + 60
        while not server._admit_order and time.monotonic() < deadline:
            time.sleep(0.001)
        f_long = server.submit(long, max_new_tokens=4)
        got_short = f_short.result(timeout=120)
        got_long = f_long.result(timeout=120)
        m = server.metrics()
    finally:
        server.stop()
    np.testing.assert_array_equal(want_short, got_short)
    np.testing.assert_array_equal(want_long, got_long)
    assert m["prefill_ticks"] >= 6  # 24 tokens / 4-token budget
    long_rec = [r for r in m["requests"] if r["decode_tokens"] == 4][0]
    assert long_rec["prefill_tokens"] >= 24
    assert long_rec["decode_overlap_ticks"] >= 2, long_rec


# ---------------------------------------------------------------------------
# ragged work packing (ISSUE 10): packed per-slot work descriptors —
# identical tokens, bounded padding


def test_ragged_pack_token_identity_and_less_waste():
    """The packed launches (per-slot work descriptors, chunk pieces of
    at most PREFILL_WINDOW_ROWS rows) emit greedy tokens IDENTICAL to
    ff.generate on a mixed chunked-prefill + decode workload, their
    padded-row share stays under a bound pinned from its value (0.4474
    in each of three runs since decode rows ride the chunk's launch: 7
    of this run's iterations hold both kinds of work, and a rider pads
    5 rows of a 6-row window where it padded a share of the (3, 1)
    launch, 51 of 114 rows against PR 29's 0.2588 over two launches an
    iteration; the one-bucket-launch-a-slot packing PR 29 deleted read
    0.5039), and the pool invariants hold after the churn."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(21)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (3, 17, 5, 11, 2)]  # two prompts prefill in chunks
    want = [ff.generate(p[None, :], max_new_tokens=6)[0] for p in prompts]
    server = ff.serve_generation(slots=3, max_len=32, paged=True,
                                 page_size=4, prefill_chunk=6)
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        m = server.metrics()
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"req {i}")
    assert m["launch_rows"] > 0
    assert 0.0 <= m["padding_waste_ratio"] < 1.0
    assert m["kernel_variant"] in ("ragged_pallas", "ragged_gather")
    assert m["padded_rows"] / m["launch_rows"] < 0.50, m
    assert m["launches"]["one_launch"] == \
        m["launches"]["iterations_with_both"] > 0
    server.pool.check_invariants(owners={})


def test_ragged_pack_preempt_mid_prefill_poolcheck_green():
    """Packed prefill under page pressure: chunked prompts racing a
    tight pool get preempted MID-PREFILL and resume; output stays
    dense-identical and the pool invariant catalog stays green (the
    ragged tick assembly must never leak or alias a page)."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(22)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in (13, 11, 9)]
    want = [ff.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    server = ff.serve_generation(slots=3, max_len=32, paged=True,
                                 page_size=4, num_pages=8,
                                 prefill_chunk=4)
    try:
        futs = [server.submit(p, max_new_tokens=5) for p in prompts]
        got = [f.result(timeout=180) for f in futs]
        m = server.metrics()
    finally:
        server.stop()
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert m["preemptions"] > 0, "pool pressure never preempted"
    assert m["pages_in_use"] == 0
    pool = server.pool
    pool.check_invariants(owners={})
    assert pool._refs == {}, pool._refs


def test_ragged_pack_is_no_option():
    """The packing is the code, not a keyword: the one public spelling
    of a server's options (serving.serve_generation, which
    FFModel.serve_generation forwards **kw to) refuses it."""
    ff, _ = _causal_lm()
    with pytest.raises(TypeError, match="ragged_pack"):
        ff.serve_generation(slots=1, max_len=16, paged=True, page_size=4,
                            ragged_pack=False)


_MEGASTEP_OPTIONS = ("megastep_ticks", "megastep_mixed", "overlap_dispatch")


def _spell(ff, spelling, option):
    """Build what `spelling` builds, with the keyword `option` set."""
    from flexflow_tpu import serving
    from flexflow_tpu.paged.scheduler import PagedGenerationServer
    from flexflow_tpu.serve_strategy import ServeStrategy
    from flexflow_tpu.spec import SpecConfig
    from flexflow_tpu.spec.server import SpeculativePagedServer

    kw = dict(slots=1, max_len=16, page_size=4, **{option: 1})
    if spelling == "serve_generation":
        return serving.serve_generation(ff, paged=True, **kw)
    if spelling == "PagedGenerationServer":
        return PagedGenerationServer(ff, **kw)
    if spelling == "SpeculativePagedServer":
        return SpeculativePagedServer(ff, SpecConfig(width=2, depth=2), **kw)
    return ServeStrategy(**{option: 1})


@pytest.mark.parametrize("spelling", [
    "serve_generation", "PagedGenerationServer", "SpeculativePagedServer",
    "ServeStrategy"])
@pytest.mark.parametrize("option", _MEGASTEP_OPTIONS)
def test_megastep_options_are_no_options(option, spelling):
    """The serving loop is the code, not a keyword: every spelling of a
    server's options refuses the three that chose a device-resident
    loop, at a value that meant "off" too."""
    ff, _ = _causal_lm()
    with pytest.raises(TypeError, match=option):
        _spell(ff, spelling, option)


@pytest.mark.parametrize("server", ["Paged", "Speculative"])
def test_strategy_kwargs_are_the_constructors_parameters(server):
    """Whatever `ServeStrategy.to_server_kwargs()` names, the server
    it configures takes: an option deleted from the constructors and
    left behind in the strategy fails here, not at the first swap."""
    import inspect

    from flexflow_tpu.paged.scheduler import PagedGenerationServer
    from flexflow_tpu.serve_strategy import ServeStrategy
    from flexflow_tpu.spec.server import SpeculativePagedServer

    cls = {"Paged": PagedGenerationServer,
           "Speculative": SpeculativePagedServer}[server]
    params = set(inspect.signature(cls.__init__).parameters)
    kw = set(ServeStrategy().to_server_kwargs(slots=2, max_len=64))
    # `paged` chooses the class and `speculate` is its `spec`
    assert kw - {"paged", "speculate"} <= params, kw - params
    assert "spec" in params or server == "Paged"


@pytest.mark.parametrize("row", [0, 1, 2], ids=["state", "window", "latent"])
def test_graph_kind_rows_name_only_options(row):
    """Every option a kind of graph refuses by name IS an option of
    `serve_generation` (`paged=False` names `paged`)."""
    import inspect

    from flexflow_tpu import serving

    params = set(inspect.signature(serving.serve_generation).parameters)
    _is_kind, refused, why = serving._GRAPH_KINDS[row]
    assert why.startswith(["state", "sliding-window", "latent"][row])
    names = {name.split("=")[0] for name in refused}
    assert names <= params, names - params


def _lifecycle_server(ff, **kw):
    """A paged server that holds its bookkeeping to the invariant
    catalog every time the host takes a launch's picks (test-only: the
    check is too hot for serving, and the server has no hook for it)."""
    from flexflow_tpu.paged.scheduler import PagedGenerationServer

    class Checked(PagedGenerationServer):
        delivered = 0

        def _deliver(self, rec):
            super()._deliver(rec)
            self._check_invariants()
            self.delivered += 1

    return Checked(ff, max_len=64, **kw)


# scenario -> (seed, prompt lengths, new tokens a request, server options,
# what it submits with)
_LIFECYCLES = {
    # a page fills while the launch that writes its last row is in flight
    "page-boundary": (2, (5,), (16,), dict(slots=2, page_size=4), {}),
    # a request ends on its length beside slots that go on
    "length-finish": (3, (4, 6), (5, 12), dict(slots=2, page_size=16), {}),
    "stop-token": (4, (5,), (10,), dict(slots=2, page_size=16), {}),
    "finish-orders": (5, (3, 5, 4, 6), (3, 7, 12, 5),
                      dict(slots=4, page_size=4), {}),
    "seeded-temperature": (1, (5,), (14,),
                           dict(slots=4, page_size=8, seed=11),
                           dict(temperature=0.8)),
    "chunk-beside-decoders": (8, (3, 24, 5), (8, 8, 8),
                              dict(slots=3, page_size=4, prefill_chunk=6),
                              {}),
    # four requests over three slots and nine pages: the youngest is
    # preempted and recomputed, a slot turns over
    "full-pool-turnover": (6, (3, 6, 5, 4), (10, 10, 10, 10),
                           dict(slots=3, page_size=4, num_pages=10), {}),
    "int8-stable": (0, (3, 6, 5), (12, 12, 12),
                    dict(slots=4, page_size=4, kv_dtype="int8"), {}),
}


@pytest.mark.parametrize("scenario", _LIFECYCLES)
def test_decode_lifecycle_matches_dense(scenario):
    """The serving loop through a request's whole life against the dense
    reference (`FFModel.generate`; the dense server at a temperature),
    the invariant catalog held at every delivery."""
    seed, lens, news, server_kw, submit_kw = _LIFECYCLES[scenario]
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, lcfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    if submit_kw:
        dense = ff.serve_generation(slots=2, max_len=64,
                                    seed=server_kw["seed"])
        try:
            want = [dense.generate(p, max_new_tokens=n, **submit_kw)
                    for p, n in zip(prompts, news)]
        finally:
            dense.stop()
    else:
        want = [ff.generate(p[None, :], max_new_tokens=n)[0]
                for p, n in zip(prompts, news)]
    if scenario == "stop-token":
        # ends on the fourth token, with launches in flight behind it
        server_kw = dict(server_kw, eos_id=int(want[0][3]))
        want = [want[0][:4]]

    def serve():
        server = _lifecycle_server(ff, **server_kw)
        try:
            futs = [server.submit(p, max_new_tokens=n, **submit_kw)
                    for p, n in zip(prompts, news)]
            got = [np.asarray(f.result(timeout=600)) for f in futs]
        finally:
            server.stop()
        assert server.delivered > 0
        server._check_invariants()
        assert server.pool.pages_in_use == 0
        return got, server.metrics()

    got, m = serve()
    assert m["requests_served"] == len(prompts)
    if scenario == "int8-stable":
        # an int8 pool is not the dense cache's numerics: its tokens are
        # its own, the same run to run, all but one request the dense's
        again, _ = serve()
        for a, b in zip(got, again):
            np.testing.assert_array_equal(a, b)
        assert m["kv_cache_dtype"] == "int8"
        assert sum(np.array_equal(w, g)
                   for w, g in zip(want, got)) >= len(prompts) - 1
        return
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    if scenario == "page-boundary":
        assert m["launches_ahead"] > 4      # pages grew under launches
    if scenario == "stop-token":
        assert m["late_stop_rows"] == 1     # the row dispatched behind it
    if scenario == "full-pool-turnover":
        assert m["preemptions"] >= 1


_SERVER_IMPORTS = """
import sys
import numpy as np
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.llama import LlamaConfig, build_llama

lcfg = LlamaConfig(vocab_size=64, dim=32, layers=1, heads=2, kv_heads=2,
                   hidden=64, rope_theta=10000.0)
ff = FFModel(FFConfig(batch_size=1, seed=7))
build_llama(ff, lcfg, batch_size=1, seq_len=8, dtype=DataType.FLOAT)
ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
before = set(sys.modules)   # compile() may import of the search what it likes
kw = {}
if SPECULATE:
    from flexflow_tpu.spec import SpecConfig
    kw["speculate"] = SpecConfig(width=2, depth=2)
server = ff.serve_generation(slots=2, max_len=16, paged=True, page_size=4,
                             prefill_chunk=4, **kw)
try:
    server.warm_launch_shapes()
    out = server.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    server.metrics()
finally:
    server.stop()
assert len(out) == 3, out
assert "flexflow_tpu.serve_strategy" in sys.modules
print("ADDED", sorted(m for m in set(sys.modules) - before
                      if m.startswith("flexflow_tpu.search")))
"""


@pytest.mark.parametrize("speculate", [False, True])
def test_paged_server_imports_nothing_of_the_search(speculate):
    """Building, warming and driving a paged server (plain or
    speculative) imports no module under flexflow_tpu.search: the
    strategy a server derives for itself lives in the serving side's
    leaf, flexflow_tpu/serve_strategy.py. In a subprocess, because this
    process has long imported the search."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         f"SPECULATE = {speculate}\n" + _SERVER_IMPORTS],
        capture_output=True, text=True, timeout=300, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": repo + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ADDED []" in proc.stdout, proc.stdout[-2000:]


def test_warm_launch_shapes_freezes_setup_objects_until_stop():
    """After warm_launch_shapes() what set-up made is out of the cyclic
    collector's reach (a generation-2 walk over jax's objects stopped the
    serving loop for 0.85 s on the chip, PERF.md section 6, PR 29);
    serving still works, and stop() hands the objects back."""
    import gc

    ff, _ = _causal_lm()
    server = ff.serve_generation(slots=2, max_len=16, paged=True,
                                 page_size=4, prefill_chunk=4)
    try:
        server.warm_launch_shapes()
        assert gc.get_freeze_count() > 10_000
        out = server.generate(np.arange(1, 6, dtype=np.int32),
                              max_new_tokens=3)
        assert len(out) == 3
    finally:
        server.stop()
    assert gc.get_freeze_count() == 0


def test_paged_submit_contract():
    """Shared submit surface: bad requests rejected, page-capacity guard,
    submit after stop raises."""
    ff, _ = _causal_lm()
    server = ff.serve_generation(slots=1, max_len=16, paged=True,
                                 page_size=4, num_pages=3)
    try:
        with pytest.raises(ValueError):
            server.submit(np.array([1, 2], np.int32), max_new_tokens=0)
        with pytest.raises(ValueError):
            server.submit(np.array([], np.int32), max_new_tokens=2)
        with pytest.raises(ValueError):  # max_len guard (shared with dense)
            server.submit(np.arange(15, dtype=np.int32), max_new_tokens=5)
        with pytest.raises(ValueError):  # page-pool capacity guard
            server.submit(np.arange(9, dtype=np.int32), max_new_tokens=3)
    finally:
        server.stop()
    with pytest.raises(RuntimeError):
        server.submit(np.array([1, 2], np.int32), max_new_tokens=2)


@pytest.mark.slow
def test_paged_stress_many_requests_long_sequences():
    """Heavy soak (excluded from the tier-1 CPU gate): TPU-sized pages,
    many overlapping requests, repeated pool churn — greedy output stays
    dense-identical throughout."""
    ff, lcfg = _causal_lm()
    rs = np.random.RandomState(6)
    prompts = [rs.randint(0, lcfg.vocab_size, (int(n),)).astype(np.int32)
               for n in rs.randint(2, 40, size=20)]
    want = [ff.generate(p[None, :], max_new_tokens=24)[0] for p in prompts]
    server = ff.serve_generation(slots=8, max_len=64, paged=True,
                                 page_size=8, num_pages=25)
    try:
        futs = [server.submit(p, max_new_tokens=24) for p in prompts]
        got = [f.result(timeout=600) for f in futs]
    finally:
        server.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert server.metrics()["pages_in_use"] == 0


def test_requeue_prefix_never_double_folds():
    """Regression (caught by the stress soak): a request preempted TWICE
    must not fold its generated prefix into the prompt twice. The prompt
    is immutable; re-prefill context is always seq_tokens() = prompt +
    tokens-so-far, idempotent across any number of preemptions."""
    from flexflow_tpu.serving import _GenRequest

    prompt = np.array([7, 8, 9], np.int32)
    req = _GenRequest(prompt, max_new=8, temperature=0.0)
    req.tokens = [1, 2, 3]
    np.testing.assert_array_equal(req.seq_tokens(),
                                  [7, 8, 9, 1, 2, 3])  # first preemption
    req.tokens.append(4)  # decoded further after re-admission
    np.testing.assert_array_equal(req.seq_tokens(),
                                  [7, 8, 9, 1, 2, 3, 4])  # second one
    np.testing.assert_array_equal(req.prompt, prompt)  # never mutated


# ---------------------------------------------------------------------------
# randomized op-sequence fuzz over the pool invariant catalog (ISSUE 9):
# the same declarative invariants the poolcheck model checker explores
# exhaustively on tiny bounds, here driven through long seeded random
# interleavings on larger configurations — breadth where BFS has depth


def test_pool_fuzz_random_op_interleavings_hold_invariants():
    """Seeded random walks over admit/prefill/decode/preempt(+resume)/
    release/defrag through the poolcheck harness (which drives the REAL
    PagePool): every state along every walk must satisfy the full
    invariant catalog — both the harness's op-scope checks and the
    PagePool.check_invariants() debug hook."""
    import random

    from flexflow_tpu.analysis import pool_invariants
    from flexflow_tpu.analysis.poolcheck import CONFIGS, PoolModel

    for config in ("base", "spec"):
        for seed in range(4):
            rng = random.Random(0xF00D + seed)
            model = PoolModel(**CONFIGS[config])
            for step in range(250):
                ops = model.enabled_ops()
                if not ops:
                    break  # every request drained
                model.violations = []
                op = rng.choice(ops)
                model.apply(op)
                assert model.violations == [], (config, seed, step, op,
                                                model.violations)
                model.pool.check_invariants(owners=model.owners())
                extra = pool_invariants.check_committed(model.pool,
                                                        model.committed)
                assert extra == [], (config, seed, step, op, extra)


def test_pool_check_invariants_debug_hook():
    """PagePool.check_invariants() passes on healthy bookkeeping (with
    and without an owners map) and names the violated invariant when
    the state is corrupted by hand."""
    pool = PagePool(num_pages=8, page_size=4, max_pages_per_seq=4)
    toks = np.arange(8, dtype=np.int32)
    chain = pool.chain_hashes(toks)
    pages = pool.alloc(2)
    pool.register_full(pages[0], chain[0])
    pool.check_invariants(owners={"req0": pages})
    pool.free(list(reversed(pages)))  # leaf-first: page 1 parks on LRU
    pool.check_invariants(owners={})

    pool._refs[pages[1]] = 1  # corrupt: page is both live and dead
    with pytest.raises(AssertionError) as ei:
        pool.check_invariants()
    msg = str(ei.value)
    assert "free-accounting" in msg or "dead-list" in msg

    del pool._refs[pages[1]]
    pool.check_invariants()  # healthy again
    with pytest.raises(AssertionError) as ei:
        # owners disagree with refcounts
        pool.check_invariants(owners={"req0": [pages[0]]})
    assert "refcount-owners" in str(ei.value)
