"""Quantized KV pages: int8 payload with per-page, per-KV-head scales.

HBM per page is the binding constraint on tokens in flight and on
prefix-cache capacity — the pool sizes admission, eviction and the LRU
dead list entirely in pages. Storing K/V as int8 with a float32 scale
sidecar quadruples the pages a fixed HBM budget holds (vs an fp32
model; 2x vs bf16) at the cost of a bounded logit error.

Layout. A quantized pool keeps, per attention node, FOUR buffers in the
caches dict instead of two::

    {"k":       (num_pages, page_size, Hkv * D)  int8,
     "v":       (num_pages, page_size, Hkv * D)  int8,
     "k_scale": (num_pages, Hkv)                 float32,
     "v_scale": (num_pages, Hkv)                 float32}

(flat-lane pages, paged/attention.py: head h owns lanes h*D..(h+1)*D of
every row; `_by_head` below is the one place that split is written.)

The scale granularity is per (page, head, K-or-V): one float per KV
head per page, symmetric around zero (stored = round(x / scale),
clipped to [-127, 127]; loaded = stored * scale). Putting the sidecar
INSIDE the caches dict is the load-bearing trick: every pool-following
operation — the COW clone's ``copy_page`` tree.map, the defrag
permutation's ``b[perm]``, the spec commit — already maps over every
leaf of that dict, so scales ride along with their pages by
construction. The poolcheck scale-sidecar
invariant (analysis/pool_invariants.py) proves that discipline holds.

Quantize-on-append with rescale-on-grow. A page's scale only ever
GROWS while the page is allocated (it resets to zero on alloc): when an
append's new rows need a larger scale, the touched pages' existing int8
rows are re-quantized to the grown scale in place (a gather/scatter over
just the B*S touched pages, not the pool). Zero-initialized scales make
empty pages dequantize to exact zeros, and a page revived from the LRU
dead list keeps its scale because it keeps its content.

The tolerance story: greedy decode against an fp32 reference stays
within a small logit tolerance (tests/test_quantized_kv.py pins it) and
speculative acceptance stays above a floor; the running max observed
output delta is exported as the ``kv_quant_error`` gauge when
FF_TPU_KV_QUANT_DEBUG=1 keeps a shadow fp32 cache (docs/paged.md).
"""

from __future__ import annotations

from typing import Optional, Tuple

QMAX = 127.0  # symmetric int8 grid: round(x / scale) in [-127, 127]

# Floor for the rescale ratio's divisor: far below any real scale but
# large enough that old_scale / SCALE_EPS stays finite in f32. Shared
# with the executor's scale-aware commit copy so both grids agree.
SCALE_EPS = 1e-30

# Canonical kv_dtype knob values -> (jnp dtype name, itemsize bytes,
# quantized?). "auto" (the default everywhere) means "the model's own
# dtype, no scale sidecar" and is deliberately absent here — callers
# treat it as None. This table is pure data so the search-side pricer
# (search/cost_model.py) can price a dtype without importing jax.
KV_DTYPES = {
    "fp32": ("float32", 4, False),
    "float32": ("float32", 4, False),
    "bf16": ("bfloat16", 2, False),
    "bfloat16": ("bfloat16", 2, False),
    "fp16": ("float16", 2, False),
    "float16": ("float16", 2, False),
    "int8": ("int8", 1, True),
}

SCALE_BYTES = 4  # the sidecar is float32 per (page, head, K-or-V)


def kv_dtype_info(kv_dtype: Optional[str]) -> Optional[Tuple[str, int, bool]]:
    """(jnp dtype name, itemsize, quantized) for a kv_dtype knob value,
    or None for "auto"/None. Raises on unknown names so a typo'd knob
    fails at validation time, not as a silent fp32 pool."""
    if kv_dtype is None or kv_dtype == "auto":
        return None
    try:
        return KV_DTYPES[kv_dtype]
    except KeyError:
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r}; expected 'auto' or one of "
            f"{sorted(set(KV_DTYPES))}") from None


def resolve_kv_dtype(kv_dtype: Optional[str]):
    """The jnp dtype for a kv_dtype knob value (None for "auto")."""
    info = kv_dtype_info(kv_dtype)
    if info is None:
        return None
    import jax.numpy as jnp

    return jnp.dtype(info[0])


def is_quantized_dtype(dtype) -> bool:
    """True when a pool at this jnp dtype needs the scale sidecar."""
    import jax.numpy as jnp

    return jnp.dtype(dtype) == jnp.int8


def scale_entry_names(bufs) -> bool:
    """True when a per-node caches dict carries the scale sidecar."""
    return "k_scale" in bufs


def _by_head(rows, n_heads: int):
    """(..., Hkv*D) flat-lane rows -> (..., Hkv, D)."""
    return rows.reshape(*rows.shape[:-1], n_heads, -1)


def rescale_pages(pages, ratio):
    """Re-quantize gathered int8 ``pages`` (..., P, Hkv*D) onto a grown
    grid: ``ratio`` (..., Hkv) is old_scale / new_scale per head."""
    import jax.numpy as jnp

    blk = _by_head(pages.astype(jnp.float32), ratio.shape[-1])
    blk = blk * ratio[..., None, :, None]
    return jnp.clip(jnp.round(blk), -QMAX, QMAX).astype(
        pages.dtype).reshape(pages.shape)


def quantize_rows(rows, scales, dtype):
    """Quantize fp ``rows`` (..., Hkv, D) at per-head ``scales``
    (..., Hkv) onto the int8 grid; returns flat-lane (..., Hkv*D)."""
    import jax.numpy as jnp

    q = jnp.clip(jnp.round(rows / scales[..., None]), -QMAX, QMAX)
    return q.astype(dtype).reshape(*rows.shape[:-2], -1)


def quantized_append(pool, scales, x, page, off, live):
    """Scatter fp rows ``x`` into an int8 ``pool`` under grow-only
    per-(page, head) ``scales``. pool: (N, P, Hkv*D) int8; scales:
    (N, Hkv) f32; x: (B, S, Hkv, D) fp; page/off/live: (B, S). Returns
    (new pool, new scales).

    Three scatters, all touching only the B*S addressed pages:
      1. grow: scatter-max each live row's needed scale (amax/127) into
         its page's sidecar entry (duplicate page indices combine
         correctly under max);
      2. rescale: re-quantize the touched pages' EXISTING rows from the
         old scale to the grown one (duplicate pages write identical
         content, so the unordered scatter is benign);
      3. write: quantize the new rows at the grown scale. Dead rows
         (live == False) are redirected to the null page by the caller
         and quantized at whatever scale page 0 has — garbage rows in
         the garbage page, same contract as the fp path. Their amax is
         excluded from step 1 so padding never inflates a real scale.
    """
    import jax.numpy as jnp

    f32 = jnp.float32
    xf = x.astype(f32)
    # typed scalar constants: a bare Python float in jnp.where/maximum
    # weak-type-promotes the whole scale pipeline (numcheck's
    # dtype-silent-promotion territory); pin them at f32
    zero = f32(0.0)
    amax = jnp.max(jnp.abs(xf), axis=-1)                     # (B, S, Hkv)
    need = jnp.where(live[..., None], amax / f32(QMAX), zero)
    new_scales = scales.at[page].max(need)
    old_t = scales[page]                                     # (B, S, Hkv)
    new_t = new_scales[page]
    ratio = jnp.where(new_t > 0, old_t / jnp.maximum(new_t, f32(SCALE_EPS)),
                      zero)
    pool = pool.at[page].set(rescale_pages(pool[page], ratio))
    s_rows = jnp.where(new_t > 0, new_t, f32(1.0))           # (B, S, Hkv)
    pool = pool.at[page, off].set(quantize_rows(xf, s_rows, pool.dtype))
    return pool, new_scales


def dequantize_pages(pages, scales):
    """pages: (..., P, Hkv*D) int8 gathered by page; scales:
    (..., Hkv) f32 gathered the same way. Returns float32 pages of the
    same flat-lane shape."""
    import jax.numpy as jnp

    blk = _by_head(pages.astype(jnp.float32), scales.shape[-1])
    return (blk * scales[..., None, :, None]).reshape(pages.shape)


def quantize_leaf(arr):
    """Per-leaf symmetric int8 fake-quantization for weight streaming
    (Executor.init_params(weight_dtype="int8")): snap every element to
    the 255-point grid scale * [-127..127] and store the result at
    bfloat16 — the matmuls downstream stay dense-float (there is no
    int8 matmul path in the executor), so this models the accuracy of
    int8 weight storage without changing any compute kernel."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(arr)) / QMAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(arr / scale), -QMAX, QMAX)
    return (q * scale).astype(jnp.bfloat16)
