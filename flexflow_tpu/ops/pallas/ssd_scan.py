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

An item of 2 to 8 live rows: the C = 8 rows of a head are solved TOGETHER
(the SSD form, arXiv:2405.21060 section 6, with an item as the chunk).
With S0 the state the item finds, xd = D x the step-scaled input (C, P), B
and C the item's (C, N) input and output maps (ONE group: every head
shares them) and G_t = a_1 + ... + a_t the head's running log-decay, a
SCALAR a row:

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

What the solve reads of a head's scalars is `small`, (B, C, H * 16)
float32 (`pack_small`, a few fused XLA operations over (B, C, H) scalars):
a head's 16 lanes hold row i's M_ij = L_ij (C B^T)_ij for j < 8 (C B^T is
ONE (8, 8) product for all heads), exp(G_i) at lane 8, exp(G_C - G_i) at
lane 9 and exp(G_C) (in every row) at lane 10. The kernel is left with
`M xd` (C broadcast multiply-adds on the vector unit), `C S0^T` and the
state's rank-C update (products on the matrix unit, float32 operands at
`Precision.HIGHEST`, as kda_scan.py's).

An item of ONE live row (a decode row, a chunk's rider, a chunk's tail of
one row) is a rank-ONE update and pays for no more, chosen on the device
from `rows[i] == 1`:

    S' = e^a S0 + xd b^T        one multiply-add a vreg of state
    y  = S' c                   one product a 128 rows of state

The state's layout puts P on sublanes and N on lanes, so `xd` has to meet
it as a COLUMN. `_one_row_small` hands it over that way: for such an item
a head's 16 lanes of `small` hold, in row s and lane k < P / 8, xd[8 k +
s], and e^a in every row of lane 10 as before; a vreg of the update is
then two lane broadcasts (the unit that permutes lanes does them cheaply;
its lane REDUCTIONS are what cost: a read-out summed along lanes read
320-480 us a layer in the kernel tool where this form reads 134) and the
read-out is one matrix-unit product of `c` against 128 rows of updated
state (two heads of 64), whose result already lies as the output does:
row, then head x P. The block is copied in first where the item starts
its slot's run and updated where it lies (reading the input block and
writing the output block in one pass measured a third SLOWER). Nothing
is eight rows wide but the product's left operand.

Heads a step (`_heads_a_step`). A launch one row wide (W == 1: the decode
launch, nine launches in ten of a decode-bound server) holds no solve, so
its kernel is the one-row form alone and a step takes as many heads as
`STATE_VMEM` holds of their states, in and out, double-buffered: all 64
of Granite-4.0-H's, 32 steps a layer where 8 heads a step were 256 (a step
costs 0.17-0.2 us by itself on the v5e); the form loops over the step's
lane tiles of 8 heads (`_one_row`). A launch eight rows wide keeps 8
heads a step, one lane tile of `small`: the solve is unrolled a head, and
on the chip it ran 1.4 times SLOWER a head at 16 heads a step and twice
at 64, unrolled or as a loop over lane tiles (PERF.md section 6, PR 52).

A row past the item's length arrives with a = 0 and xd = 0: its column of
M meets a zero row of xd, its row of the update is zero, it changes
nothing; its own read-out is not read. An item WITHOUT rows (`rows[i] ==
0`) does no solve and reads out zeros; where it is the first of a run it
still copies or zeroes the state block (`start`, `fresh`). A decode row is
an item of one live row: its cost is its state's way in and out, 2 x 2 MB
a slot and layer, which the one-row form's instructions stay under: in
the model the decode launch's scan reads 209 us a layer, 642 GB/s of the
HBM's 819, where the eight-row solve at 8 heads a step read 354.

Both kernels' name, `ssd_ragged_scan`, is what the trace readers match.
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
TILE = LANES // SMALL               # heads a lane tile of `small`
KEEP = ROWS + 2     # the lane of `small` that holds exp(G_C) in every row
# what a step's state blocks may take of VMEM: a head's (P, N) float32, in
# and out, double-buffered
STATE_VMEM = 8 << 20


def _dot(x, y, contract):
    """A float32 product on the matrix unit at full float32 precision."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _solve(group, p, xd_ref, b_in, c_out, small_ref, s_ref, o_ref):
    """An item's eight rows, a head at a time, on the state where it lies."""
    for g in range(group):
        lanes = slice(g * p, (g + 1) * p)
        xd = xd_ref[0, :, lanes]                          # (C, P)
        small = small_ref[0, :, g * SMALL:(g + 1) * SMALL]
        s0 = s_ref[0, g]                                  # (P, N)
        y = small[:, ROWS:ROWS + 1] * _dot(c_out, s0, ((1,), (1,)))
        for j in range(ROWS):                             # + M xd
            y = y + small[:, j:j + 1] * xd[j:j + 1]
        o_ref[0, :, lanes] = y
        add = _dot(small[:, ROWS + 1:ROWS + 2] * xd, b_in, ((0,), (0,)))
        # exp(G_C) arrives in EVERY row of its lane, a column over a
        # sublane tile: Mosaic broadcasts along lanes or along
        # sublanes, not one value both ways
        keep = small[:, KEEP:KEEP + 1]                    # (C, 1)
        for r in range(0, p, ROWS):
            s_ref[0, g, r:r + ROWS] = (keep * s0[r:r + ROWS]
                                       + add[r:r + ROWS])


def _one_row(group, p, b_row, c_rows, small_ref, s_ref, o_ref):
    """An item of one live row: the rank-one update a vreg of state at a
    time, the read-out one product a lane tile of the output (LANES / P
    heads). `small_ref` holds the item's xd as columns (`_one_row_small`);
    c_rows (ROWS, N) has the live row's map in row 0, which is the row of
    the product that `o_ref` keeps (the others are not read). A lane tile
    of `small` (TILE heads) is written out; a wider step LOOPS over its
    tiles, because 64 heads written out cost every set-up 6 s of tracing
    and lowering on the chip's host (PERF.md section 6, PR 52)."""
    per = LANES // p

    def tile(j):
        """Heads j * TILE .. + TILE; j is 0 or the loop's index."""
        def at(lane):
            return lane if isinstance(j, int) else pl.multiple_of(lane,
                                                                  LANES)

        cols = small_ref[0, :, pl.ds(at(j * LANES), LANES)]
        for t in range(TILE // per):
            updated = []
            for g in range(t * per, (t + 1) * per):
                col = cols[:, g * SMALL:(g + 1) * SMALL]
                keep = col[:, KEEP:KEEP + 1]              # (ROWS, 1): e^a
                for k in range(p // ROWS):
                    rs = slice(k * ROWS, (k + 1) * ROWS)
                    s1 = (keep * s_ref[0, j * TILE + g, rs]
                          + col[:, k:k + 1] * b_row)
                    s_ref[0, j * TILE + g, rs] = s1
                    updated.append(s1)
            y = _dot(c_rows, jnp.concatenate(updated, axis=0), ((1,), (1,)))
            lanes = pl.ds(at(j * (TILE * p) + t * LANES), LANES)
            o_ref[0, :, lanes] = y[:o_ref.shape[1]]

    if group == TILE:
        tile(0)
    else:
        jax.lax.fori_loop(0, group // TILE, lambda j, _: tile(j), None)


def _kernel(start_ref, fresh_ref, _slot_ref, rows_ref, *refs, group, p,
            wide):
    """`wide`: the launch is ROWS rows wide and its items may hold a
    solve (refs then start with xd); a launch one row wide holds items of
    one live row and items without rows alone."""
    if wide:
        xd_ref, b_ref, c_ref, small_ref, s_in_ref, o_ref, s_out_ref = refs
    else:
        b_ref, c_ref, small_ref, s_in_ref, o_ref, s_out_ref = refs
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

    @pl.when(rows_ref[i] == 1)
    def _():
        c_rows = c_ref[0]
        if not wide:        # the product's left operand is a sublane tile
            c_rows = jnp.broadcast_to(c_rows, (ROWS, c_rows.shape[-1]))
        _one_row(group, p, b_ref[0, 0:1], c_rows, small_ref, s_out_ref,
                 o_ref)

    if wide:
        @pl.when(rows_ref[i] > 1)
        def _():
            _solve(group, p, xd_ref, b_ref[0], c_ref[0], small_ref,
                   s_out_ref, o_ref)


def available(head_dim: int, state_dim: int, heads: int,
              interpret: bool) -> bool:
    """Heads of 16, 32 or 64: a head's xd is at most ROWS columns of
    ROWS in `small`, and whole heads fill a lane tile of the output."""
    return interpret or (jax.default_backend() == "tpu"
                         and state_dim % LANES == 0
                         and head_dim in (16, 32, 64)
                         and heads % TILE == 0)


def _heads_a_step(heads: int, head_dim: int, state_dim: int,
                 width: int) -> int:
    """Heads a grid step takes (module docstring): one lane tile of
    `small` where the launch may hold a solve, else the most tiles whose
    states, in and out and double-buffered, fit `STATE_VMEM`."""
    if width > 1:
        return TILE
    fit = STATE_VMEM // (4 * head_dim * state_dim * 4)
    return max(g for g in range(TILE, heads + 1, TILE)
               if heads % g == 0 and (g <= fit or g == TILE))


def pack_small(a, cb):
    """a (B, C, H) float32 log-decays (0 on dead rows), cb (B, C, C) = C
    B^T -> `small` (B, C, H * SMALL): what the solve reads of a head's
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


def _one_row_small(xd0, a0):
    """xd0 (B, H * P) and a0 (B, H), the ONE live row of each item -> what
    the one-row form reads, (B, ROWS, H * SMALL): in a head's 16 lanes,
    row s and lane k < P / ROWS hold xd0[8 k + s] (a vreg of the state's
    rows is multiplied by one lane of it, spread along lanes), and every
    row of lane `KEEP` e^a, where `pack_small` puts exp(G_C)."""
    B, H = a0.shape
    cols = xd0.shape[-1] // (H * ROWS)                         # P / ROWS
    x = jnp.moveaxis(xd0.reshape(B, H, cols, ROWS), 3, 1)      # (b, s, h, k)
    keep = jnp.broadcast_to(jnp.exp(a0)[:, None, :, None], (B, ROWS, H, 1))
    return jnp.concatenate(
        [x, jnp.zeros((B, ROWS, H, KEEP - cols), jnp.float32), keep,
         jnp.zeros((B, ROWS, H, SMALL - KEEP - 1), jnp.float32)],
        axis=-1).reshape(B, ROWS, H * SMALL)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def ssd_ragged_scan(xd, b_in, c_out, a, state, slots, start, fresh, rows,
                    *, heads, interpret=False):
    """xd (= D x) (B, W, heads * P) float32, zero on dead rows; b_in,
    c_out (B, W, N) float32; a (B, W, heads) float32 log-decays, zero on
    dead rows; W is 1 (every item has one live row or none) or at most
    ROWS; state (slots, heads, P, N) float32; slots, start, fresh (B,)
    int32: the slot an item's state lies in (a pad item names its
    neighbour's), whether the item is the first of its slot's run in this
    launch (the state is read from `state`), whether it starts a request
    (the state starts at zero); rows (B,) int32: an item's live rows (0:
    the item's solve is skipped and its read-out is zero). Returns (y (B,
    W, heads * P) = S_t C_t a row, the state array with the named slots'
    states after the launch)."""
    B, W, c = xd.shape
    p = c // heads
    n = b_in.shape[-1]
    wide = W > 1
    group = _heads_a_step(heads, p, n, W)
    assert W <= ROWS and heads % group == 0, (xd.shape, heads)
    assert LANES % p == 0 and ROWS <= p <= ROWS * ROWS, (xd.shape, heads)
    assert state.shape[1:] == (heads, p, n), (xd.shape, state.shape)
    small = _one_row_small(xd[:, 0], a[:, 0])
    if wide:
        pad = ((0, 0), (0, ROWS - W), (0, 0))
        xd, b_in, c_out, a = (jnp.pad(t, pad) for t in (xd, b_in, c_out, a))
        cb = jnp.einsum("bin,bjn->bij", c_out, b_in,
                        precision=jax.lax.Precision.HIGHEST)
        small = jnp.where((rows == 1)[:, None, None], small,
                          pack_small(a, cb))
    rows_in = xd.shape[1]

    def of_item(h, i, *_):
        return (i, 0, h)

    def shared(h, i, *_):
        return (i, 0, 0)

    def of_slot(h, i, _start, _fresh, slot, _rows):
        return (slot[i], h, 0, 0)

    row_spec = pl.BlockSpec((1, rows_in, group * p), of_item)
    map_spec = pl.BlockSpec((1, rows_in, n), shared)
    state_spec = pl.BlockSpec((1, group, p, n), of_slot)
    small_spec = pl.BlockSpec((1, ROWS, group * SMALL), of_item)
    operands = [b_in, c_out, small, state]
    in_specs = [map_spec, map_spec, small_spec, state_spec]
    if wide:            # only the solve reads xd as rows
        operands, in_specs = [xd] + operands, [row_spec] + in_specs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(heads // group, B), in_specs=in_specs,
        out_specs=[row_spec, state_spec])
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, group=group, p=p, wide=wide),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, rows_in, c), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the state is written where it lies (its operand's number counts
        # the four prefetched scalars)
        input_output_aliases={3 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * STATE_VMEM),
        interpret=interpret, name="ssd_ragged_scan",
    )(start, fresh, slots, rows, *operands)
    return y[:, :W], new_state
