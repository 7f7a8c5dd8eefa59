"""The state-space scan of a Mamba-2 layer over a RAGGED launch
(Mamba2Attrs; ops/mamba2.py has the layer around it).

The grid and the state's residency are `kda_scan.py`'s: a launch is B work
items of 8 rows (an 8-row piece of a prefill chunk, or a decode row and
seven dead ones); the grid is (groups of heads, items), items innermost;
the state of a head, (P, N) float32, is the OUTPUT block indexed by the
item's slot, so it stays in VMEM while consecutive items name the same
slot and goes back to HBM once a slot and launch; the stored state is read
once a slot too, or not at all where the run starts a request (`fresh`).
The state array is aliased in place: slots no item names are not touched.

Inside an item the C = 8 rows of a head are solved TOGETHER (the SSD form,
arXiv:2405.21060 section 6, with an item as the chunk). With S0 the state
the item finds, xd = D x the step-scaled input (C, P), B and C the item's
(C, N) input and output maps (ONE group: every head shares them) and
G_t = a_1 + ... + a_t the head's running log-decay, a SCALAR a row:

    L  = lower(exp(G_i - G_j))                                  (C, C)
    Y  = (L * (C B^T)) xd + exp(G) * (C S0^T)                   (C, P)
    S' = exp(G_C) S0 + (exp(G_C - G) xd)^T B                    (P, N)

The exponents: KDA's kernel multiplies by exp(-G), which its BOUNDED gate
allows (|G| <= 40 an item). Here a = -exp(A_log) softplus(.) is unbounded
below (a step's log-decay can be -20 or less), so every exponent is a
DIFFERENCE that is <= 0: G_i - G_j for i >= j, G_C - G_t, G_t itself.
Nothing overflows, and what underflows is a contribution that is zero to
float32 in the recurrence too. The scalar decay is what makes that
possible without a second pass: L is one (C, C) matrix a head.

What the caller prepares (ops/mamba2.py `paged_mixer`, a few fused XLA
operations over (B, C, H) scalars): `small`, (B, C, H * 16) float32, a
head's 16 lanes holding row i's M_ij = L_ij (C B^T)_ij for j < 8 (C B^T is
ONE (8, 8) product for all heads), exp(G_i) at lane 8, exp(G_C - G_i) at
lane 9 and exp(G_C) (in every row) at lane 10. The kernel is left with
`M xd` (C broadcast multiply-adds on the vector unit), `C S0^T` and the
state's rank-C update (products on the
matrix unit, float32 operands at `Precision.HIGHEST`, as kda_scan.py's).

A row past the item's length arrives with a = 0 and xd = 0: its column of
M meets a zero row of xd, its row of the update is zero, it changes
nothing; its own read-out is not read. An item WITHOUT rows (`rows[i] ==
0`) does no solve and reads out zeros; where it is the first of a run it
still copies or zeroes the state block (`start`, `fresh`). A decode row is
an item of one live row: its cost is its state's way in and out.

Its name, `ssd_ragged_scan`, is what the trace readers match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 8            # rows of an item: the float32 sublane tile
SMALL = 16          # lanes a head takes in `small`
HEADS_A_STEP = LANES // SMALL       # a step's `small` block is one lane tile


def _dot(x, y, contract):
    """A float32 product on the matrix unit at full float32 precision."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(start_ref, fresh_ref, _slot_ref, rows_ref, xd_ref, b_ref, c_ref,
            small_ref, s_in_ref, o_ref, s_out_ref, *, group, p):
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
        b_in, c_out = b_ref[0], c_ref[0]                      # (C, N)
        for g in range(group):
            lanes = slice(g * p, (g + 1) * p)
            xd = xd_ref[0, :, lanes]                          # (C, P)
            small = small_ref[0, :, g * SMALL:(g + 1) * SMALL]
            s0 = s_out_ref[0, g]                              # (P, N)
            y = small[:, ROWS:ROWS + 1] * _dot(c_out, s0, ((1,), (1,)))
            for j in range(ROWS):                             # + M xd
                y = y + small[:, j:j + 1] * xd[j:j + 1]
            o_ref[0, :, lanes] = y
            add = _dot(small[:, ROWS + 1:ROWS + 2] * xd, b_in, ((0,), (0,)))
            # exp(G_C) arrives in EVERY row of lane 10, a column over a
            # sublane tile: Mosaic broadcasts along lanes or along
            # sublanes, not one value both ways
            keep = small[:, ROWS + 2:ROWS + 3]                # (C, 1)
            for r in range(0, p, ROWS):
                s_out_ref[0, g, r:r + ROWS] = (keep * s0[r:r + ROWS]
                                               + add[r:r + ROWS])


def available(head_dim: int, state_dim: int, heads: int,
              interpret: bool) -> bool:
    return interpret or (jax.default_backend() == "tpu"
                         and state_dim % LANES == 0
                         and (HEADS_A_STEP * head_dim) % LANES == 0
                         and heads % HEADS_A_STEP == 0)


def pack_small(a, cb):
    """a (B, C, H) float32 log-decays (0 on dead rows), cb (B, C, C) = C
    B^T -> `small` (B, C, H * SMALL): what the kernel reads of a head's
    scalars (module docstring). Every exponent is <= 0."""
    B, C, H = a.shape
    g = jnp.cumsum(a, axis=1)                                  # (B, C, H)
    diff = g[:, :, None, :] - g[:, None, :, :]                 # (B, i, j, H)
    lower = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :])[None, :, :,
                                                               None]
    m = jnp.where(lower, jnp.exp(jnp.minimum(diff, 0.0)), 0.0) * cb[..., None]
    cols = jnp.stack([jnp.exp(g), jnp.exp(g[:, -1:] - g),
                      jnp.broadcast_to(jnp.exp(g[:, -1:]), g.shape)], axis=-1)
    small = jnp.concatenate(
        [jnp.moveaxis(m, 2, 3), cols,
         jnp.zeros((B, C, H, SMALL - C - 3), jnp.float32)], axis=-1)
    return small.reshape(B, C, H * SMALL)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def ssd_ragged_scan(xd, b_in, c_out, small, state, slots, start, fresh,
                    rows, *, heads, interpret=False):
    """xd (= D x) (B, ROWS, heads * P) float32, zero on dead rows; b_in,
    c_out (B, ROWS, N) float32; small (B, ROWS, heads * SMALL)
    (`pack_small`); state (slots, heads, P, N) float32; slots, start, fresh
    (B,) int32: the slot an item's state lies in (a pad item names its
    neighbour's), whether the item is the first of its slot's run in this
    launch (the state is read from `state`), whether it starts a request
    (the state starts at zero); rows (B,) int32: an item's live rows (0:
    the item's solve is skipped and its read-out is zero). Returns (y (B,
    ROWS, heads * P) = S_t C_t a row, the state array with the named
    slots' states after the launch)."""
    B, W, c = xd.shape
    p = c // heads
    n = b_in.shape[-1]
    group = HEADS_A_STEP
    assert W == ROWS and heads % group == 0, (xd.shape, heads)
    assert state.shape[1:] == (heads, p, n), (xd.shape, state.shape)

    def of_item(h, i, *_):
        return (i, 0, h)

    def shared(h, i, *_):
        return (i, 0, 0)

    def of_slot(h, i, _start, _fresh, slot, _rows):
        return (slot[i], h, 0, 0)

    row_spec = pl.BlockSpec((1, W, group * p), of_item)
    map_spec = pl.BlockSpec((1, W, n), shared)
    state_spec = pl.BlockSpec((1, group, p, n), of_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(heads // group, B),
        in_specs=[row_spec, map_spec, map_spec,
                  pl.BlockSpec((1, W, group * SMALL), of_item), state_spec],
        out_specs=[row_spec, state_spec])
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, group=group, p=p), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, W, c), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the state is written where it lies (operand 8 counts the four
        # prefetched scalars)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ssd_ragged_scan",
    )(start, fresh, slots, rows, xd, b_in, c_out, small, state)
    return y, new_state
