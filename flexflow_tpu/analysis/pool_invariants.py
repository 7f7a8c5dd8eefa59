"""Declarative invariant catalog for the paged serving state machine.

ONE catalog, consumed by three clients:

  * the poolcheck model checker (analysis/poolcheck.py) asserts every
    entry at every reachable state of its bounded exploration;
  * `PagePool.check_invariants()` (paged/pool.py) runs the pool-scope
    entries as a debug hook — the randomized op-sequence fuzz test in
    tests/test_paged.py calls it after every op;
  * docs/paged.md renders the catalog as the invariant table that
    replaced the old prose guarantees (each entry's name is the
    poolcheck finding code, `inv-<name>`).

Pool-scope entries take only the pool (plus an optional owners map);
op-scope entries (cow-write, defrag-preserve) are enforced by the model
checker AT THE MUTATING OPERATION, where the write/remap is visible —
they have no `check` function here, only the spec the checker implements.

This module is dependency-free on purpose: paged/pool.py imports it
lazily inside check_invariants(), and analysis/poolcheck.py imports it
eagerly, so neither direction creates an import cycle.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Invariant:
    """One catalog entry. `name` doubles as the poolcheck finding code
    suffix (`inv-<name>`); `scope` is where it can be evaluated:

      pool    — a function of the PagePool alone (check(pool));
      owners  — needs the live owner map {owner_id: [pages]} the
                scheduler/harness holds (check(pool, owners));
      rows    — needs the per-page committed-row counts only the model
                checker tracks (check(pool, committed));
      scales  — needs the quantized-pool scale-sidecar mirror the model
                checker tracks (check(pool, scale_of, content_tag));
      window  — needs a server's window-class tables and its requests'
                block maps (check(tables, rows, window, page_size));
      state   — needs a server's account of its slots' recurrent states
                (check(states, live, launched, leaves));
      op      — only observable at the mutating operation itself; the
                model checker enforces it inline (check is None).
    """

    name: str
    scope: str
    description: str
    check: Optional[Callable] = None


# ---------------------------------------------------------------------------
# pool-scope checks (each returns a list of "name: detail" violations)


def _free_accounting(pool) -> List[str]:
    v = []
    free, lru, refs = set(pool._free), set(pool._lru), set(pool._refs)
    if len(pool._free) != len(free):
        v.append(f"free list holds duplicates: {sorted(pool._free)}")
    for a, b, la, lb in ((free, lru, "free", "lru"),
                         (free, refs, "free", "refs"),
                         (lru, refs, "lru", "refs")):
        both = a & b
        if both:
            v.append(f"pages {sorted(both)} are in both {la} and {lb}")
    everywhere = free | lru | refs
    if 0 in everywhere:
        v.append("null page 0 entered the allocator")
    bad = [p for p in everywhere if not 1 <= p < pool.num_pages]
    if bad:
        v.append(f"out-of-range page ids {sorted(bad)}")
    total = len(free) + len(lru) + len(refs)
    if total != pool.capacity:
        v.append(f"free({len(free)}) + cached({len(lru)}) + "
                 f"live({len(refs)}) = {total} != capacity "
                 f"{pool.capacity}")
    bad_refs = {p: r for p, r in pool._refs.items() if r < 1}
    if bad_refs:
        v.append(f"non-positive refcounts {bad_refs}")
    return [f"free-accounting: {m}" for m in v]


def _dead_list(pool) -> List[str]:
    v = []
    for p in pool._lru:
        if p in pool._refs:
            v.append(f"page {p} is dead-cached AND refcounted")
        if not pool._keys_of.get(p):
            v.append(f"page {p} is dead-cached but has no hash-index "
                     "entry (unhittable; it should be on the free list)")
    for p, keys in pool._keys_of.items():
        if keys and p not in pool._refs and p not in pool._lru:
            v.append(f"page {p} is hash-registered ({keys}) but neither "
                     "live nor dead-cached — a lookup would revive a "
                     "freed page")
    return [f"dead-list: {m}" for m in v]


def _index(pool) -> List[str]:
    v = []
    for h, p in pool._full.items():
        if ("full", h) not in pool._keys_of.get(p, []):
            v.append(f"full entry {h[:8]} -> {p} missing from the "
                     "inverse index")
    for h, (p, toks) in pool._partial.items():
        if ("partial", h) not in pool._keys_of.get(p, []):
            v.append(f"partial entry {h[:8]} -> {p} missing from the "
                     "inverse index")
        if not 0 < len(toks) < pool.page_size:
            v.append(f"partial entry {h[:8]} -> {p} has {len(toks)} "
                     f"tail tokens (must be in (0, page_size))")
    for p, keys in pool._keys_of.items():
        for kind, h in keys:
            if kind == "full" and pool._full.get(h) != p:
                v.append(f"inverse entry ('full', {h[:8]}) on page {p} "
                         f"points elsewhere ({pool._full.get(h)})")
            elif kind == "partial" and \
                    pool._partial.get(h, (None,))[0] != p:
                v.append(f"inverse entry ('partial', {h[:8]}) on page "
                         f"{p} points elsewhere")
    return [f"index: {m}" for m in v]


def _refcount_owners(pool, owners: Dict[object, Sequence[int]]
                     ) -> List[str]:
    held: Dict[int, int] = {}
    for pages in owners.values():
        for p in pages:
            held[p] = held.get(p, 0) + 1
    v = []
    for p in set(held) | set(pool._refs):
        if pool._refs.get(p, 0) != held.get(p, 0):
            v.append(f"page {p}: refcount {pool._refs.get(p, 0)} != "
                     f"{held.get(p, 0)} live owner-table references")
    return [f"refcount-owners: {m}" for m in v]


def _spec_scratch(pool, committed: Dict[int, int]) -> List[str]:
    """Published pages hold only COMMITTED K/V rows: a full entry
    implies every row committed; a partial entry implies at least its
    registered tail rows. Tree scratch (rows written past the committed
    head by speculative verify) must never reach the index."""
    v = []
    for h, p in pool._full.items():
        c = committed.get(p, 0)
        if c < pool.page_size:
            v.append(f"full-registered page {p} has only {c}/"
                     f"{pool.page_size} committed rows (scratch or "
                     "unwritten rows were published)")
    for h, (p, toks) in pool._partial.items():
        c = committed.get(p, 0)
        if c < len(toks):
            v.append(f"partial-registered page {p} names {len(toks)} "
                     f"tail rows but only {c} are committed")
    return [f"spec-scratch: {m}" for m in v]


def _scale_sidecar(pool, scale_of: Dict[int, int],
                   content_tag: Dict[int, int]) -> List[str]:
    """A quantized pool's scale sidecar must follow pages through every
    pool op. `content_tag` is the spec's ground truth — what the scale
    entry OUGHT to describe given the page's content history (stamped at
    every row write, copied by the COW clone, permuted by defrag, reset
    at allocation); `scale_of` mirrors what the implementation's sidecar
    actually holds. They must agree on every page whose content is
    reachable (live or dead-cached) — a page whose int8 payload is
    dequantized under another page's scale is silent corruption."""
    v = []
    for p in sorted(set(pool._refs) | set(pool._lru)):
        s, c = scale_of.get(p, 0), content_tag.get(p, 0)
        if s != c:
            v.append(f"page {p}: sidecar scale state {s} does not match "
                     f"its content state {c} (the scale entry was "
                     "dropped, leaked across a realloc, or left behind "
                     "by a page move)")
    return [f"scale-sidecar: {m}" for m in v]


def _tier_partition(pool) -> List[str]:
    """With a host tier attached (disagg/host_tier.py), every hash is in
    EXACTLY one place: resident (pool._full, owning a device page) or
    spilled (a tier entry holding the host payload) — never both, never
    neither-with-a-page. A hash resident AND spilled would let the two
    copies diverge (a COW writer re-registers, the stale spilled copy
    later fetches over it); a tier entry is by definition
    registered-but-NOT-resident."""
    tier = getattr(pool, "_tier", None)
    if tier is None:
        return []
    v = []
    spilled = set(tier.hashes())
    both = spilled & set(pool._full)
    if both:
        v.append(f"hashes {sorted(h[:8] for h in both)} are resident "
                 "AND spilled — the hash index is no longer a partition")
    if tier.occupancy_pages > tier.capacity_pages:
        v.append(f"tier holds {tier.occupancy_pages} entries over its "
                 f"capacity {tier.capacity_pages}")
    return [f"tier-partition: {m}" for m in v]


def _tier_scales(pool, tier_scale_of: Dict[str, int],
                 tier_content_tag: Dict[str, int]) -> List[str]:
    """Scales travel on spill and fetch: every spilled payload carries
    the scale-sidecar state its content was quantized under.
    `tier_content_tag` is the spec's ground truth (the content state the
    page had when it spilled); `tier_scale_of` mirrors the scale the
    implementation actually packed into the payload. A spilled page
    fetched under the wrong (or a zeroed) scale dequantizes to garbage
    on a different server — silent cross-worker corruption."""
    tier = getattr(pool, "_tier", None)
    if tier is None:
        return []
    v = []
    for h in tier.hashes():
        s = tier_scale_of.get(h, 0)
        c = tier_content_tag.get(h, 0)
        if s != c:
            v.append(f"spilled entry {h[:8]}: payload scale state {s} "
                     f"does not match its content state {c} (the scale "
                     "sidecar was dropped on spill or fetch)")
    return [f"tier-scales: {m}" for m in v]


def _window_class(tables, rows: Dict[int, Tuple[Dict[int, int], int]],
                  window: int, page_size: int) -> List[str]:
    """`tables` is the window class's (slots, max_pages) table; `rows`
    maps a live slot to (its request's {block: page} map, the next row
    the request writes)."""
    v = []
    seen: Dict[int, int] = {}
    for slot, (held, nxt) in sorted(rows.items()):
        row = tables[slot]
        mapped = {int(b): int(row[b]) for b in range(len(row)) if row[b]}
        if mapped != {int(b): int(p) for b, p in held.items()}:
            v.append(f"slot {slot}: table maps {mapped}, its request "
                     f"holds {dict(held)}")
        for b, page in mapped.items():
            if page in seen:
                v.append(f"page {page} is in the tables of slots "
                         f"{seen[page]} and {slot}")
            seen[page] = slot
        # every committed row a query at `nxt` still sees is backed
        first = max(nxt - window + 1, 0) // page_size
        last = (nxt - 1) // page_size if nxt > 0 else -1
        gone = [b for b in range(first, last + 1) if b not in mapped]
        if gone:
            v.append(f"slot {slot}: rows of blocks {gone} are inside the "
                     f"window of row {nxt} and their pages were released")
    idle = [s for s in range(len(tables)) if s not in rows
            and tables[s].any()]
    if idle:
        v.append(f"slots {idle} hold no request and their tables are "
                 "not null")
    return [f"window-class: {m}" for m in v]


def _slot_state(states: Sequence[Tuple[Optional[int], int]],
                live: Dict[int, Tuple[int, int]],
                launched: Sequence[Tuple[int, int, int]],
                leaves: Sequence[Tuple[str, tuple, tuple]] = ()
                ) -> List[str]:
    """`states[slot]` is (the request a slot's recurrent state belongs
    to or None, the rows it holds) as the scheduler accounts for it;
    `live` maps a live slot to (its request, the next row it writes);
    `launched` is the newest launch's live items (slot, first row, rows)
    in launch order; `leaves` names each state leaf the server holds
    with (its shape and dtype name) as held and as its op's
    `state_specs` declares them for this many slots (a KDA node's
    (slots, H, d, d) float32 and 3 H d lanes of conv rows, a Mamba-2
    node's (slots, H, P, N) float32 and H P + 2 N lanes)."""
    v = []
    for name, held, declared in leaves:
        if held != declared:
            v.append(f"leaf {name}: holds {held}, its op declares "
                     f"{declared}")
    owned: Dict[int, int] = {}
    for slot, (owner, rows) in enumerate(states):
        if owner is None:
            continue
        if owner in owned:
            v.append(f"request {owner} owns the states of slots "
                     f"{owned[owner]} and {slot}")
        owned[owner] = slot
        if slot not in live or live[slot][0] != owner:
            v.append(f"slot {slot}: its state belongs to request {owner}, "
                     f"which is not live there")
        elif rows != live[slot][1]:
            v.append(f"slot {slot}: its state holds {rows} rows and its "
                     f"request writes row {live[slot][1]} next (a state "
                     "is zero at admission and follows every row)")
    unowned = [s for s in live if states[s][0] is None]
    if unowned:
        v.append(f"live slots {unowned} have no state of their own")
    runs: Dict[int, int] = {}
    prev = None
    for slot, first, rows in launched:
        if slot in runs and (prev != slot or runs[slot] != first):
            v.append(f"slot {slot}: the launch's items are not "
                     "consecutive and in row order")
        runs[slot] = first + rows
        prev = slot
    return [f"slot-state: {m}" for m in v]


CATALOG: Tuple[Invariant, ...] = (
    Invariant(
        "free-accounting", "pool",
        "free + dead-cached + live page counts sum to capacity; the "
        "three sets are disjoint, in range, and never contain the null "
        "page; refcounts are positive",
        _free_accounting),
    Invariant(
        "dead-list", "pool",
        "a page is on the LRU dead list iff its refcount is 0 AND it is "
        "hash-registered; every registered page is live or dead-cached, "
        "never free",
        _dead_list),
    Invariant(
        "index", "pool",
        "the full/partial hash indexes and the per-page inverse index "
        "(_keys_of) agree exactly; partial tails name 1..page_size-1 "
        "rows",
        _index),
    Invariant(
        "refcount-owners", "owners",
        "every page's refcount equals the number of live owner-table "
        "references to it (checked at operation boundaries)",
        _refcount_owners),
    Invariant(
        "spec-scratch", "rows",
        "pages named by the hash index hold only committed K/V rows — "
        "speculative tree scratch is never registered before its commit",
        _spec_scratch),
    Invariant(
        "scale-sidecar", "scales",
        "every reachable page's quantization-scale sidecar entry "
        "describes that page's current content: scales are reset with "
        "the page at allocation, copied by the COW clone, remapped by "
        "the defrag permutation, and kept by LRU revival — never "
        "dropped, leaked across a realloc, or left at a moved page's "
        "old slot",
        _scale_sidecar),
    Invariant(
        "tier-partition", "pool",
        "with a host tier attached, resident ⊎ spilled partitions the "
        "hash index: a tiered page is registered-but-not-resident (its "
        "hash is in the tier, not in _full), no hash is in both, and "
        "the tier never exceeds its capacity",
        _tier_partition),
    Invariant(
        "tier-scales", "tier-scales",
        "scales travel with their page through the host tier: every "
        "spilled payload carries the scale-sidecar state of the content "
        "it was read from, and a fetch restores both together",
        _tier_scales),
    Invariant(
        "window-class", "window",
        "where sliding-window layers have a class of pages of their own "
        "(a second PagePool, held to every pool-scope entry above): a "
        "window table maps exactly the pages its request holds, no page "
        "is in two tables, an idle slot's table is null, and no row "
        "inside the window of a request's next row lies behind a "
        "released page",
        _window_class),
    Invariant(
        "slot-state", "state",
        "where layers keep a recurrent state a slot beside the pages: a "
        "state belongs to exactly one request, live in that slot; it is "
        "zero at admission (it holds no row) and holds exactly the rows "
        "its request has written since; a launch's items of one slot are "
        "consecutive and in row order, and no launch names the state of "
        "an idle slot (the launch's items are checked against the "
        "states' owners before a request's last launch vacates it); every "
        "state leaf is indexed by slot at the shape and dtype its op "
        "declares, whichever state op it is",
        _slot_state),
    Invariant(
        "cow-write", "op",
        "no row write lands in a page the writer does not own, a page "
        "with refcount != 1, or rows a hash-index entry has published "
        "(shared pages are written only via the COW clone helper)"),
    Invariant(
        "defrag-preserve", "op",
        "defrag returns a true permutation that fixes the null page and "
        "rewrites refcounts, LRU order, both hash indexes, and every "
        "owner's page list by the same old→new bijection"),
)


def by_name(name: str) -> Invariant:
    for entry in CATALOG:
        if entry.name == name:
            return entry
    raise KeyError(name)


def check_pool(pool, owners: Optional[Dict[object, Sequence[int]]] = None
               ) -> List[str]:
    """Run every pool-scope invariant (and refcount-owners when an
    owners map is given). Returns 'name: detail' violation strings."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "pool":
            v += entry.check(pool)
        elif entry.scope == "owners" and owners is not None:
            v += entry.check(pool, owners)
    return v


def check_window_class(tables, rows, window: int, page_size: int
                       ) -> List[str]:
    """Run the window-scope invariant over a server's window-class
    tables (paged/scheduler.py `_check_invariants`)."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "window":
            v += entry.check(tables, rows, window, page_size)
    return v


def check_slot_state(states, live, launched, leaves=()) -> List[str]:
    """Run the state-scope invariant over a server's account of its
    slots' recurrent states and over its state leaves
    (paged/scheduler.py `_check_invariants`)."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "state":
            v += entry.check(states, live, launched, leaves)
    return v


def check_committed(pool, committed: Dict[int, int]) -> List[str]:
    """Run the committed-rows invariants (model checker / fuzz harness
    only — the live scheduler does not track per-page committed rows)."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "rows":
            v += entry.check(pool, committed)
    return v


def check_scales(pool, scale_of: Dict[int, int],
                 content_tag: Dict[int, int]) -> List[str]:
    """Run the quantized-pool scale-sidecar invariants (model checker
    only — the live scheduler keeps the sidecar inside the caches dict,
    where the checker's mirror tracks it at op granularity)."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "scales":
            v += entry.check(pool, scale_of, content_tag)
    return v


def check_tier_scales(pool, tier_scale_of: Dict[str, int],
                      tier_content_tag: Dict[str, int]) -> List[str]:
    """Run the host-tier scale-travel invariants over the attached
    tier's spilled entries (model checker only — the live tier stores
    scales inside its opaque payloads)."""
    v: List[str] = []
    for entry in CATALOG:
        if entry.scope == "tier-scales":
            v += entry.check(pool, tier_scale_of, tier_content_tag)
    return v
