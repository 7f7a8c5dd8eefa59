"""The delta-rule scan of a KDA layer over a RAGGED launch
(KdaAttentionAttrs; ops/kda_attention.py has the layer around it).

A launch is B work items of W rows (an 8-row piece of a prefill chunk, or
a decode row); consecutive items may belong to one slot, whose state then
passes from item to item. The grid is (groups of heads, items), items
innermost: the state of a head, (d_k, d_v) float32, is the OUTPUT block indexed by
the item's slot, so it stays in VMEM while consecutive items name the
same slot and goes back to HBM once a slot and launch; the stored state
is read once a slot too (an input block under the same index), or not at
all where the run starts a request (`fresh`: the state starts at zero, on
the device). The state array is aliased in place: slots no item names are
not touched.

Inside an item the C = 8 rows of a head are solved TOGETHER, in the
chunked (WY) form of the gated delta rule, whose heavy terms are matrix
products. With S0 the state the item finds, G_t = a_1 + ... + a_t the
running log-decay (a channel, all <= 0) and kb = beta k:

    K+ = k exp(G)      Q+ = q exp(G)      Kb- = kb exp(-G)    (C, d_k)
    L  = strictly_lower(K+ Kb-^T)     A = lower(Q+ Kb-^T)     (C, C)
    D  = (I + L)^-1 (V - K+ S0)                               (C, d_v)
    O  = Q+ S0 + A D
    S' = Diag(exp G_C) S0 + (kb exp(G_C - G))^T D

Row t of D is the recurrence's v_t - k_t^T S'_t, row t of O its read-out
S_t^T q_t. `[K+; Q+] S0`, `[K+; Q+] Kb-^T` (the step's heads in ONE
product: a head reads its own block, the cross terms are finite and
ignored) and the state's update are products on the matrix unit with
float32 operands at `Precision.HIGHEST` (Mosaic's `contract_precision
<fp32>`: six bfloat16 passes, float32 sums); (I + L)^-1 is a forward
substitution over the C rows, `A D` C outer products, both on the vector
unit. kb exp(G_C - G) and exp(G_C) are needed as COLUMNS over d_k (a
row scales a line of S): their C + 1 rows are transposed once an item.

The exponents: the gate bounds a in (-5, 0), so |G| <= 5 C = 40 across an
item: exp(-G) <= e^40 is finite in float32, each term of L and A is a
product of two correctly rounded factors and keeps its relative accuracy,
and exp(G_C - G), exp(G_C), exp(G) are <= 1. It does NOT stretch to 16
rows: a pass of the product takes the third bfloat16 piece of an operand,
2^-16 of it, and under k exp(-80) that piece is subnormal and flushed (on
the chip 16 rows at a = -5 read an error of 3e-3 where 8 read 3e-6). A
longer block needs a reference row a sub-block, both exponents <= 0. An
item is the block here: the grid and the state's residency are what a
row-by-row solve had.

A row past the item's length arrives with a = 0 and kb = 0: its column of
L and A and its row of the update are zero, it changes nothing. An item
WITHOUT rows (`rows[i] == 0`: a launch's filler, a slot that idles) does
no solve and reads out zeros; where it is the first of a run it still
copies or zeroes the state block (`start`, `fresh`), so the run behind
it finds its state. A grid step takes `HEADS_A_STEP` heads of an item.

Its name, `kda_ragged_scan`, is what the trace readers match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 8        # rows of an item: the float32 sublane tile
HEADS_A_STEP = 8


def _columns(x):
    """(rows, d) -> (d, rows): the rows as columns over d (on the chip the
    block is padded to a square first: the 128 x 128 transpose is the one
    every Mosaic version has)."""
    rows, d = x.shape
    if rows < d:
        x = jnp.concatenate([x, jnp.zeros((d - rows, d), x.dtype)], axis=0)
    return x.T[:, :rows]


def _dot(x, y, contract=((1,), (0,))):
    """A float32 product on the matrix unit at full float32 precision."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _running_sum(a):
    """(C, d) -> the sum of rows 0..t in row t, by doubling."""
    C = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    shift = 1
    while shift < C:
        a = a + jnp.where(row >= shift, pltpu.roll(a, shift, 0), 0.0)
        shift *= 2
    return a


def _solve_blocks(q, k, kb, v, a, s_ref):
    """A block of C rows for each of the step's heads: lists of (C, d)
    arrays a head, `s_ref[0, h]` head h's state (d_k, d_v), updated in
    place -> [o (C, d_v) a head]. The module's docstring has the form;
    the heads are independent, so each stage is written for all of them
    and the heads' small products against `Kb-` are ONE product (a
    head's own block of it is read, the cross terms are finite and
    ignored)."""
    C, heads = q[0].shape[0], len(q)
    g = [_running_sum(x) for x in a]                      # G_t, all <= 0
    up = [jnp.exp(x) for x in g]
    kq = [jnp.concatenate([k[h] * up[h], q[h] * up[h]], axis=0)
          for h in range(heads)]                          # K+ over Q+
    pair = _dot(jnp.concatenate(kq, axis=0),
                jnp.concatenate([kb[h] * jnp.exp(-g[h])
                                 for h in range(heads)], axis=0),
                ((1,), (1,)))                             # (heads 2C, heads C)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, heads * C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, heads * C), 1)
    out = []
    for h in range(heads):
        into = _dot(kq[h], s_ref[0, h])                   # (2C, d_v)
        at = 2 * C * h
        lower = jnp.where(row > col - C * h, pair[at:at + C], 0.0)      # L
        reach = jnp.where(row >= col - C * h, pair[at + C:at + 2 * C],
                          0.0)                                          # A
        # D = (I + L)^-1 (V - K+ S0) by substitution: row s is final once
        # the rows before it were taken out of the rows below
        delta = v[h] - into[:C]
        for s in range(C - 1):
            c = C * h + s
            delta = delta - lower[:, c:c + 1] * delta[s:s + 1]
        o = into[C:]
        for s in range(C):                                # O = Q+ S0 + A D
            c = C * h + s
            o = o + reach[:, c:c + 1] * delta[s:s + 1]
        out.append(o)
        # kb exp(G_C - G) and exp(G_C) as columns over d_k: one transpose
        g_end = g[h][C - 1:C]
        cols = _columns(jnp.concatenate(
            [kb[h] * jnp.exp(g_end - g[h]), jnp.exp(g_end)], axis=0))
        s_ref[0, h] = (cols[:, C:C + 1] * s_ref[0, h]
                       + _dot(cols[:, :C], delta))
    return out


def _kernel(start_ref, fresh_ref, _slot_ref, rows_ref, q_ref, k_ref, kb_ref,
            v_ref, a_ref, s_in_ref, o_ref, s_out_ref, *, group, d):
    i = pl.program_id(1)

    @pl.when(start_ref[i] == 1)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(fresh_ref[i] == 1)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    @pl.when(rows_ref[i] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rows_ref[i] > 0)
    def _():
        lanes = [slice(g * d, (g + 1) * d) for g in range(group)]
        o = _solve_blocks(*([ref[0, :, at] for at in lanes] for ref in
                            (q_ref, k_ref, kb_ref, v_ref, a_ref)),
                          s_out_ref)
        for at, o_head in zip(lanes, o):
            o_ref[0, :, at] = o_head


def available(head_dim: int, interpret: bool) -> bool:
    return interpret or (jax.default_backend() == "tpu"
                         and head_dim == LANES)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def kda_ragged_scan(q, k, kb, v, a, state, slots, start, fresh, rows, *,
                    heads, interpret=False):
    """q, k, kb (= beta k), v, a: (B, ROWS, heads * d) float32, dead rows
    with a = 0 and kb = 0; state (N, heads, d, d) float32; slots, start,
    fresh (B,) int32: the slot an item's state lies in (a pad item names
    its neighbour's), whether the item is the first of its slot's run in
    this launch (the state is read from `state`), whether it starts a
    request (the state starts at zero); rows (B,) int32: an item's live
    rows (0: the item's solve is skipped and its read-out is zero).
    Returns (o (B, ROWS, heads * d), the state array with the named
    slots' states after the launch)."""
    B, W, c = q.shape
    d = c // heads
    assert W == ROWS and state.shape[1:] == (heads, d, d), (q.shape,
                                                            state.shape)
    # heads a grid step: fewer, longer steps (a step costs about 0.35 us
    # whatever it does) and one product against `Kb-` for all of them;
    # eight heads' states, in and out and double-buffered, are 2 MB of VMEM
    group = next(g for g in (HEADS_A_STEP, 4, 2, 1) if heads % g == 0)

    def of_item(h, i, *_):
        return (i, 0, h)

    def of_slot(h, i, _start, _fresh, slot, _rows):
        return (slot[i], h, 0, 0)

    row_spec = pl.BlockSpec((1, W, group * d), of_item)
    state_spec = pl.BlockSpec((1, group, d, d), of_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(heads // group, B),
        in_specs=[row_spec] * 5 + [state_spec],
        out_specs=[row_spec, state_spec])
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, group=group, d=d), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, W, c), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the state is written where it lies (operand 9 counts the four
        # prefetched scalars)
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_ragged_scan",
    )(start, fresh, slots, rows, q, k, kb, v, a, state)
    return o, new_state
