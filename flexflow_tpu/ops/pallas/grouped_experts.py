"""Grouped expert matmuls for a DROPLESS expert layer (ExpertShareAttrs).

Rows are sorted by expert and every expert's group is padded to whole
row tiles of `tm`, so a tile belongs to ONE expert and needs no mask.
The grid is (column blocks, row tiles) with the tile -> expert map
scalar-prefetched: a weight block is `w[tile_expert[m], :, n]`, so

  * consecutive tiles of one expert keep the block (Pallas skips a copy
    whose block index did not change): an expert's weights are read
    once a column block, whatever its rows;
  * an expert with no rows has no tile and is never read from HBM;
  * tiles past the last active one (the grid is the static worst case,
    rows // tm + experts) repeat the last active tile's indices and skip
    their body: no copy, no compute.

`grouped_swiglu` is silu(x Wg) * (x Wu) in one pass over x; `grouped_dot`
is the down projection. Both are named so that the device trace finds
them (`moe_grouped_swiglu`, `moe_grouped_down`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_W_BLOCK_BYTES = 4 << 20      # one weight block; two matrices, two buffers
_VMEM_LIMIT = 64 << 20


def row_tile(assignments: int, groups: int, dtype) -> int:
    """Rows a tile holds: about the mean group, whole sublane tiles of
    `dtype`, at most the MXU's 128 (a short tile costs the matrix unit
    what a full one does, loading the weight tile dominates; it saves
    the padding)."""
    sublane = 8 * (4 // jnp.dtype(dtype).itemsize)
    mean = -(-assignments // max(groups, 1))
    return int(min(128, max(sublane, -(-mean // sublane) * sublane)))


def num_tiles(assignments: int, groups: int, tm: int) -> int:
    """The static worst case: every group with rows ends in one partly
    filled tile."""
    return assignments // tm + min(groups, assignments)


def layout(local_ids, groups: int, tm: int):
    """local_ids: (A,) int32, a row's group in [0, groups) or `groups`
    for a row that belongs to none (an expert held elsewhere). Returns
    (dest (A,) the row's place in the padded layout, or rows = n_tiles *
    tm for none; tile_group (n_tiles,); n_active (1,); counts (groups,)).
    A counting sort: a row's rank in its group is the running count of
    the group's one-hot column, so rows keep their order and nothing is
    sorted, searched or scattered."""
    A = local_ids.shape[0]
    n_tiles = num_tiles(A, groups, tm)
    onehot = (local_ids[:, None] == jnp.arange(groups, dtype=jnp.int32)
              ).astype(jnp.int32)                               # (A, G)
    running = jnp.cumsum(onehot, axis=0)
    counts = running[-1]
    tiles_per = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_per)
    n_active = tile_end[-1]
    first_row = (tile_end - tiles_per) * tm
    # one-hot rows have one 1 (or none): the sums pick the row's own group
    place = jnp.sum(onehot * (first_row[None, :] + running - 1), axis=1)
    dest = jnp.where(local_ids < groups, place, n_tiles * tm).astype(
        jnp.int32)
    tile = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                       jnp.maximum(n_active - 1, 0))
    tile_group = jnp.minimum(
        jnp.sum(tile_end[None, :] <= tile[:, None], axis=1),
        groups - 1).astype(jnp.int32)
    return dest, tile_group, n_active.reshape(1).astype(jnp.int32), counts


def _col_block(k: int, n: int, itemsize: int) -> int:
    if n % LANES:
        return n
    for tn in (2048, 1024, 512, 256, 128):
        if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES:
            return tn
    return LANES


def clamp(g, u, limit: float):
    """The SwiGLU clamp on the two products before the activation:
    (min(g, L), clip(u, -L, L)); `limit` 0 is off."""
    if not limit:
        return g, u
    return jnp.minimum(g, limit), jnp.clip(u, -limit, limit)


def _kernel(tg_ref, na_ref, x_ref, *refs, swiglu, limit):
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        x = x_ref[...]
        a = lax.dot_general(x, refs[0][...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if swiglu:
            b = lax.dot_general(x, refs[1][...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            a, b = clamp(a, b, limit)
            a = a * jax.nn.sigmoid(a) * b
        o_ref[...] = a.astype(o_ref.dtype)


def _grouped(x, weights, tile_group, n_active, *, tm, out_dtype, name,
             interpret, limit=0.0):
    M, K = x.shape
    G, _, N = weights[0].shape
    tn = _col_block(K, N, weights[0].dtype.itemsize)
    n_tiles = M // tm

    def row(n, m, tg, na):
        return jnp.minimum(m, jnp.maximum(na[0] - 1, 0))

    w_spec = pl.BlockSpec((None, K, tn),
                          lambda n, m, tg, na: (tg[m], 0, n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, n_tiles),
        in_specs=[pl.BlockSpec((tm, K), lambda n, m, tg, na:
                               (row(n, m, tg, na), 0))]
        + [w_spec] * len(weights),
        out_specs=pl.BlockSpec((tm, tn), lambda n, m, tg, na:
                               (row(n, m, tg, na), n)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, swiglu=len(weights) == 2, limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(tile_group, n_active, x, *weights)


def grouped_swiglu(x, w_gate, w_up, tile_group, n_active, *, tm,
                   limit=0.0, interpret=False):
    """x: (rows, d) in the padded layout; w_gate, w_up: (G, d, f). Returns
    (rows, f) = silu(x Wg_g) * (x Wu_g), each tile with its group's
    weights; with `limit` L > 0 the two products are clamped first
    (`clamp`), in the kernel's epilogue. Rows of tiles that are not
    active are not written."""
    return _grouped(x, (w_gate, w_up), tile_group, n_active, tm=tm,
                    out_dtype=x.dtype, name="moe_grouped_swiglu",
                    interpret=interpret, limit=float(limit))


def grouped_dot(x, w, tile_group, n_active, *, tm, out_dtype=None,
                interpret=False):
    """x: (rows, f); w: (G, f, d) -> (rows, d)."""
    return _grouped(x, (w,), tile_group, n_active, tm=tm,
                    out_dtype=out_dtype or x.dtype, name="moe_grouped_down",
                    interpret=interpret)
