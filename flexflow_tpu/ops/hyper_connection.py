"""The lowering of HyperConnectionAttrs: a residual path of n streams
mixed around every block by coefficients computed from the token's own
row (ops/attrs.py has the equations). Every part is a function of ONE
row, so one lowering serves the dense forward and a ragged serving
launch alike: a launch's dead rows mix garbage that nobody reads.

Coefficients in float32: the projection of the normed row at the
HIGHEST precision (n C x (2 n + n n) a row: 0.8 MFLOP at 16,384 x 24), the
Sinkhorn rounds written out (a 4 x 4 matrix a row; `sinkhorn_iters` is a
static). The streams stay in the activations' dtype; their mix is
accumulated in float32 and rounded once.

The device operations of all four parts run under the named scope
`hc_mix`, by which a device trace tells the mixing from the blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCOPE = "hc_mix"
F32 = jnp.float32


def sinkhorn(m, iters: int, eps: float):
    """(..., n, n) positive -> rows then columns divided by their sums
    (+ eps), `iters` times: the last division is by columns."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(attrs, x, params):
    """x (..., n C) -> (Hpre (..., n), Hpost (..., n), Hres (..., n, n)),
    float32."""
    n = attrs.streams
    xf = x.astype(F32)
    xn = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                        + attrs.norm_eps)
    z = jnp.dot(xn, params["phi"].astype(F32),
                precision=lax.Precision.HIGHEST)
    alpha, b = params["alpha"].astype(F32), params["b"].astype(F32)
    scale = jnp.concatenate([jnp.broadcast_to(alpha[i], (width,))
                             for i, width in enumerate((n, n, n * n))])
    z = z * scale + b
    h_pre = jax.nn.sigmoid(z[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    h_res = sinkhorn(jnp.exp(z[..., 2 * n:]).reshape(z.shape[:-1] + (n, n)),
                     attrs.sinkhorn_iters, attrs.eps)
    return h_pre, h_post, h_res


def _streams(attrs, x):
    return x.reshape(x.shape[:-1] + (attrs.streams, -1))


def expand(attrs, x):
    with jax.named_scope(SCOPE):
        return jnp.tile(x, (1,) * (x.ndim - 1) + (attrs.streams,))


def pre(attrs, x, params):
    """X -> (h = Hpre X in X's dtype, coef = [Hpost | vec Hres] float32)."""
    with jax.named_scope(SCOPE):
        h_pre, h_post, h_res = coefficients(attrs, x, params)
        h = jnp.sum(h_pre[..., None] * _streams(attrs, x).astype(F32),
                    axis=-2)
        coef = jnp.concatenate(
            [h_post, h_res.reshape(h_res.shape[:-2] + (-1,))], axis=-1)
        return h.astype(x.dtype), coef


def post(attrs, x, coef, y):
    """X' = Hres X + Hpost^T y."""
    n = attrs.streams
    with jax.named_scope(SCOPE):
        h_post = coef[..., :n]
        h_res = coef[..., n:].reshape(coef.shape[:-1] + (n, n))
        xs = _streams(attrs, x).astype(F32)
        # n is 4: the sums are written out as broadcasts, not a matmul
        mixed = sum(h_res[..., :, j, None] * xs[..., j, None, :]
                    for j in range(n))
        out = mixed + h_post[..., None] * y.astype(F32)[..., None, :]
        return out.reshape(x.shape).astype(x.dtype)


def collapse(attrs, x):
    with jax.named_scope(SCOPE):
        return jnp.sum(_streams(attrs, x).astype(F32), axis=-2).astype(
            x.dtype)
