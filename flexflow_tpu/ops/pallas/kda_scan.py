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

Inside an item the rows are solved one at a time, exactly as the
recurrence is written, with the state as sixteen (8, 128) vector
registers' worth of float32:

    S' = Diag(exp a_t) S          u = k_t^T S'          d = v_t - u
    S  = S' + (beta_t k_t) d^T    o_t = S'^T q_t + (q_t . beta_t k_t) d

(the last line is S^T q_t with the update multiplied out, so that S' is
read once for both sums). A row past the item's length arrives with
a = 0 and beta k = 0 and changes nothing. q, k, beta k and exp(a) are
needed as COLUMNS over d_k (a row scales a line of S): the four (W, 128)
blocks of a head are stacked and transposed once an item on the way in.
A grid step takes `HEADS_A_STEP` heads of an item, one after another.

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
HEADS_A_STEP = 4


def _columns(x):
    """(rows, d) -> (d, rows): the rows as columns over d (on the chip the
    block is padded to a square first: the 128 x 128 transpose is the one
    every Mosaic version has)."""
    rows, d = x.shape
    if rows < d:
        x = jnp.concatenate([x, jnp.zeros((d - rows, d), x.dtype)], axis=0)
    return x.T[:, :rows]


def _kernel(start_ref, fresh_ref, _slot_ref, q_ref, k_ref, kb_ref, v_ref,
            a_ref, s_in_ref, o_ref, s_out_ref, *, group, d):
    i = pl.program_id(1)

    @pl.when(start_ref[i] == 1)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(fresh_ref[i] == 1)
    def _():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)

    for g in range(group):          # the step's heads, one after another
        lanes = slice(g * d, (g + 1) * d)
        s = s_out_ref[0, g]                              # (d_k, d_v)
        # q, k, beta k and the decay as columns over d_k: one transpose
        cols = _columns(jnp.concatenate(
            [q_ref[0, :, lanes], k_ref[0, :, lanes], kb_ref[0, :, lanes],
             jnp.exp(a_ref[0, :, lanes])], axis=0))      # (d_k, 4 ROWS)
        v = v_ref[0, :, lanes]
        for t in range(ROWS):
            q_c, k_c, kb_c, decay_c = (
                cols[:, n * ROWS + t:n * ROWS + t + 1] for n in range(4))
            s = s * decay_c
            u = jnp.sum(s * k_c, axis=0, keepdims=True)          # (1, d_v)
            read = jnp.sum(s * q_c, axis=0, keepdims=True)
            delta = v[t:t + 1, :] - u
            s = s + kb_c * delta
            qk = jnp.sum(q_c * kb_c, axis=0, keepdims=True)      # (1, 1)
            o_ref[0, t:t + 1, lanes] = read + qk * delta
        s_out_ref[0, g] = s


def available(head_dim: int, interpret: bool) -> bool:
    return interpret or (jax.default_backend() == "tpu"
                         and head_dim == LANES)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def kda_ragged_scan(q, k, kb, v, a, state, slots, start, fresh, *, heads,
                    interpret=False):
    """q, k, kb (= beta k), v, a: (B, ROWS, heads * d) float32, dead rows
    with a = 0 and kb = 0; state (N, heads, d, d) float32; slots, start,
    fresh (B,) int32: the slot an item's state lies in (a pad item names
    its neighbour's), whether the item is the first of its slot's run in
    this launch (the state is read from `state`), whether it starts a
    request (the state starts at zero). Returns (o (B, ROWS, heads * d),
    the state array with the named slots' states after the launch)."""
    B, W, c = q.shape
    d = c // heads
    assert W == ROWS and state.shape[1:] == (heads, d, d), (q.shape,
                                                            state.shape)
    # heads a grid step: fewer, longer steps (a step costs about 0.35 us
    # whatever it does), within a few hundred KB of VMEM
    group = next(g for g in (HEADS_A_STEP, 2, 1) if heads % g == 0)

    def rows(h, i, *_):
        return (i, 0, h)

    def of_slot(h, i, _start, _fresh, slot):
        return (slot[i], h, 0, 0)

    row_spec = pl.BlockSpec((1, W, group * d), rows)
    state_spec = pl.BlockSpec((1, group, d, d), of_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(heads // group, B),
        in_specs=[row_spec] * 5 + [state_spec],
        out_specs=[row_spec, state_spec])
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, group=group, d=d), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, W, c), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the state is written where it lies (operand 8 counts the three
        # prefetched scalars)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_ragged_scan",
    )(start, fresh, slots, q, k, kb, v, a, state)
    return o, new_state
