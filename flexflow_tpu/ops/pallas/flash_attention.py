"""Blockwise flash attention as a Pallas TPU kernel, with a custom VJP.

Replaces the reference's cuDNN multiHeadAttn path (src/ops/attention.cu,
SURVEY.md §2.2) with a TPU-native kernel: q/k/v stream HBM→VMEM in blocks,
scores are computed on the MXU in fp32 and reduced with an online softmax
(running max + denominator held in VMEM scratch), so the S×T score matrix
never touches HBM. The backward pass recomputes scores from the saved
logsumexp (standard flash-attention recomputation) with one kernel for dq
and one for dk/dv.

Layouts: the PUBLIC path operates directly on the model's (B, S, H, D)
tensors — the (batch, head) pair is folded into the outer grid axis and
the head dim is squeezed out of each block, so no transpose to a
head-major layout ever materializes in HBM (the r2-r4 benches paid
~1.6 GB/step of such transposes plus their backward mirrors at the 1b
config; tools/hlo_transpose_audit.py). GQA is handled by the kernel index
maps (each q head reads kv head h // rep), so the head repeat and its
backward reduce-sum never materialize either, and dk/dv come out at the
UNREPEATED kv head count. The ring path (ring_flash.py) keeps the older
(BH, S, D) kernels, whose statistics-carry variants it drives step by
step; both share the same block-math bodies. The logsumexp residual is
stored 128-lane-broadcast fp32 so backward reads stay in native tiling.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _causal_mask(s, iq, ik, bq, bk):
    qpos = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _live(causal, iq, ik, bq, bk):
    """Blocks fully past the diagonal are masked out under causal
    attention — their compute is skipped entirely."""
    return (iq * bq + bq - 1 >= ik * bk) if causal else (ik >= 0)


# ---------------------------------------------------------------------------
# shared block-math bodies (2D tiles; every kernel variant calls these)


def _online_block(q, k, v, m_scr, l_scr, acc_scr, scale, causal, iq, ik,
                  bq, bk):
    """One (bq, bk) tile of the online softmax: fold k/v's scores into the
    carried (m, l, acc) statistics."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, iq, ik, bq, bk)
    m_prev = m_scr[:, 0:1]
    l_prev = l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * corr + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _dq_block(q, k, v, do, lse, delta, dq_scr, scale, causal, iq, ik,
              bq, bk):
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, iq, ik, bq, bk)
    p = jnp.exp(s - lse[:, 0:1])
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, 0:1]) * scale
    dq_scr[:] += lax.dot_general(ds.astype(k.dtype), k,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)


def _dkv_block(q, k, v, do, lse, delta, dk_scr, dv_scr, scale, causal,
               iq, ik, bq, bk):
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, iq, ik, bq, bk)
    p = jnp.exp(s - lse[:, 0:1])
    # dv += pᵀ @ do ; contract the q dim of both
    dv_scr[:] += lax.dot_general(p.astype(do.dtype), do,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, 0:1]) * scale
    dk_scr[:] += lax.dot_general(ds.astype(q.dtype), q,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# flat-lane kernels: tensors stay in the PROJECTION layout (B, S, H*D) and
# the grid's head coordinate selects a D-wide LANE block — legal TPU tiling
# (the lane dim is sliced at 128-aligned offsets), no head-major transpose,
# and GQA resolved by indexing kv head h // rep. Requires D % 128 == 0; the
# public entry falls back to the (BH, S, D) transpose path otherwise.


def _fwd_kernel_bshd(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale, causal, nk, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _online_block(q_ref[...], k_ref[...], v_ref[...], m_scr, l_scr,
                      acc_scr, scale, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _fwd_bshd(q, k, v, causal, scale, bq, bk, interpret, H, D):
    """q: (B,S,H*D); k,v: (B,T,Hkv*D). Returns out (B,S,H*D) and
    lse (B,S,H*LANES) fp32."""
    B, S, _ = q.shape
    T, Hkv = k.shape[1], k.shape[2] // D
    rep = H // Hkv
    nq, nk = S // bq, T // bk
    qmap = lambda b, i, j: (b // H, i, b % H)            # noqa: E731
    kvmap = lambda b, i, j: (b // H, j, (b % H) // rep)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel_bshd, scale=scale, causal=causal,
                          nk=nk, bq=bq, bk=bk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, D), qmap),
            pl.BlockSpec((None, bk, D), kvmap),
            pl.BlockSpec((None, bk, D), kvmap),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, D), qmap),
            pl.BlockSpec((None, bq, LANES), qmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, S, H * LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _dq_kernel_bshd(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                    dq_scr, *, scale, causal, nk, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _dq_block(q_ref[...], k_ref[...], v_ref[...], do_ref[...],
                  lse_ref[...], dl_ref[...], dq_scr, scale, causal, iq, ik,
                  bq, bk)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel_bshd(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                     dv_ref, dk_scr, dv_scr, *, scale, causal, nq, nt, bq,
                     bk):
    """Grid (B*Hkv, nk, rep*nq): the innermost axis sweeps every (q head
    in the kv group) x (q block), accumulating this kv block's dk/dv
    across the whole group — GQA's head-repeat backward without ever
    materializing repeated k/v or a reduce over repeats."""
    ik, t = pl.program_id(1), pl.program_id(2)
    iq = t % nq

    @pl.when(t == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _dkv_block(q_ref[...], k_ref[...], v_ref[...], do_ref[...],
                   lse_ref[...], dl_ref[...], dk_scr, dv_scr, scale, causal,
                   iq, ik, bq, bk)

    @pl.when(t == nt - 1)
    def _():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_bshd(q, k, v, out, lse, do, causal, scale, bq, bk, interpret,
              H, D):
    B, S, _ = q.shape
    T, Hkv = k.shape[1], k.shape[2] // D
    rep = H // Hkv
    nq, nk = S // bq, T // bk
    # delta_i = Σ_d dO_id · O_id per head, lane-broadcast like lse
    delta = jnp.einsum("bshd,bshd->bsh",
                       do.reshape(B, S, H, D).astype(jnp.float32),
                       out.reshape(B, S, H, D).astype(jnp.float32))
    delta = jnp.broadcast_to(delta[..., None],
                             (B, S, H, LANES)).reshape(B, S, H * LANES)

    qmap = lambda b, i, j: (b // H, i, b % H)            # noqa: E731
    kvmap = lambda b, i, j: (b // H, j, (b % H) // rep)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_bshd, scale=scale, causal=causal,
                          nk=nk, bq=bq, bk=bk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, D), qmap),
            pl.BlockSpec((None, bk, D), kvmap),
            pl.BlockSpec((None, bk, D), kvmap),
            pl.BlockSpec((None, bq, D), qmap),
            pl.BlockSpec((None, bq, LANES), qmap),
            pl.BlockSpec((None, bq, LANES), qmap),
        ],
        out_specs=pl.BlockSpec((None, bq, D), qmap),
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # q-side blocks walk (head-in-group, q block) on the innermost axis
    gqmap = lambda g, j, t: (g // Hkv, t % nq,           # noqa: E731
                             (g % Hkv) * rep + t // nq)
    gkvmap = lambda g, j, t: (g // Hkv, j, g % Hkv)      # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_bshd, scale=scale, causal=causal,
                          nq=nq, nt=rep * nq, bq=bq, bk=bk),
        grid=(B * Hkv, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((None, bq, D), gqmap),
            pl.BlockSpec((None, bk, D), gkvmap),
            pl.BlockSpec((None, bk, D), gkvmap),
            pl.BlockSpec((None, bq, D), gqmap),
            pl.BlockSpec((None, bq, LANES), gqmap),
            pl.BlockSpec((None, bq, LANES), gqmap),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), gkvmap),
            pl.BlockSpec((None, bk, D), gkvmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Hkv * D), k.dtype),
            jax.ShapeDtypeStruct((B, T, Hkv * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bshd(q, k, v, causal, scale, bq, bk, interpret, H, D):
    out, _ = _fwd_bshd(q, k, v, causal, scale, bq, bk, interpret, H, D)
    return out


def _flash_bshd_fwd(q, k, v, causal, scale, bq, bk, interpret, H, D):
    out, lse = _fwd_bshd(q, k, v, causal, scale, bq, bk, interpret, H, D)
    return out, (q, k, v, out, lse)


def _flash_bshd_bwd(causal, scale, bq, bk, interpret, H, D, res, do):
    q, k, v, out, lse = res
    return _bwd_bshd(q, k, v, out, lse, do, causal, scale, bq, bk,
                     interpret, H, D)


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


# ---------------------------------------------------------------------------
# (BH, S, D) forward — kept for the ring path (ring_flash.py drives the
# statistics-carry variant hop by hop on per-shard head-major blocks)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, nk, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _online_block(q_ref[0], k_ref[0], v_ref[0], m_scr, l_scr, acc_scr,
                      scale, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd(q, k, v, causal, scale, bq, bk, interpret):
    """q,k,v: (BH, S|T, D). Returns out (BH,S,D), lse (BH,S,128) fp32."""
    BH, S, D = q.shape
    T = k.shape[1]
    nq, nk = S // bq, T // bk
    grid = (BH, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               nk=nk, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# ring-step forward: same online softmax, but the (m, l, acc) statistics
# carry IN from previous ring steps and OUT to the next — one call per
# rotating k/v block (used by ring_flash_attention)


def _fwd_carry_kernel(q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                      m_out, l_out, acc_out, m_scr, l_scr, acc_scr,
                      *, scale, causal, nk, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = m_in[0]
        l_scr[:] = l_in[0]
        acc_scr[:] = acc_in[0]

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _online_block(q_ref[0], k_ref[0], v_ref[0], m_scr, l_scr, acc_scr,
                      scale, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _():
        m_out[0] = m_scr[:]
        l_out[0] = l_scr[:]
        acc_out[0] = acc_scr[:]


def _fwd_carry(q, k, v, m, l, acc, causal, scale, bq, bk, interpret):
    """One ring step: fold k/v's contribution into carried (m, l, acc).
    q: (BH,S,D); k,v: (BH,T,D); m,l: (BH,S,LANES) f32; acc: (BH,S,D) f32."""
    BH, S, D = q.shape
    T = k.shape[1]
    nq, nk = S // bq, T // bk
    kernel = functools.partial(_fwd_carry_kernel, scale=scale, causal=causal,
                               nk=nk, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, m, l, acc)


# ---------------------------------------------------------------------------
# (BH, S, D) backward — ring path support


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_scr,
               *, scale, causal, nk, bq, bk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _dq_block(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                  dl_ref[0], dq_scr, scale, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, nq, bq, bk):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_live(causal, iq, ik, bq, bk))
    def _():
        _dkv_block(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                   dl_ref[0], dk_scr, dv_scr, scale, causal, iq, ik, bq, bk)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, scale, bq, bk, interpret):
    BH, S, D = q.shape
    T = k.shape[1]
    nq, nk = S // bq, T // bk
    # delta_i = Σ_d dO_id · O_id, lane-broadcast like lse
    delta = jnp.einsum("bsd,bsd->bs", do.astype(jnp.float32),
                       out.astype(jnp.float32))
    delta = jnp.broadcast_to(delta[..., None], (BH, S, LANES))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, nk=nk,
                          bq=bq, bk=bk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, nq=nq,
                          bq=bq, bk=bk),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP wrapper over (BH, S, D) layout (ring path)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bk, interpret):
    out, _ = _fwd(q, k, v, causal, scale, bq, bk, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret):
    out, lse = _fwd(q, k, v, causal, scale, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, bq, bk, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, causal, scale, bq, bk, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry


def _pick_block(n: int, want: int) -> Optional[int]:
    for b in (want, 512, 256, 128):
        if b <= n and n % b == 0:
            return b
    return n if n % LANES == 0 else None


def flash_attention_available(S: int, T: int, *, dropout: float = 0.0,
                              interpret: bool = False) -> bool:
    """True when the Pallas path supports these shapes on this backend.
    FF_TPU_NO_FLASH=1 disables every flash dispatch site (plain, ring,
    Ulysses) — A/B runs and kernel-bug escape hatch."""
    import os

    if os.environ.get("FF_TPU_NO_FLASH") == "1":
        return False
    if dropout > 0.0:
        return False
    if _pick_block(S, 512) is None or _pick_block(T, 512) is None:
        return False
    return interpret or jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = False, scale: float = 1.0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Flash attention. q: (B,S,H,D); k,v: (B,T,Hkv,D) with H % Hkv == 0.
    Returns (B,S,H,D) in q.dtype; softmax statistics accumulate in fp32.

    When D is a lane multiple the kernels consume the flat projection
    layout (B,S,H*D) directly — the grid's head coordinate picks a
    128-aligned lane block, so neither a head-major transpose nor a
    kv-head repeat ever materializes in HBM (GQA is resolved by the index
    maps). Smaller head dims fall back to the padded (BH,S,D) transpose
    path. `interpret=True` runs the Pallas interpreter (CPU tests);
    it is never chosen for the caller — off a TPU the compiled kernel
    simply fails. Default blocking is picked by head dim (measured on v5e,
    fwd+bwd at S=1024-4096): d<=64 runs ~16-20% faster at 1024x1024
    blocks, while d=128 doubles the VMEM footprint per tile and prefers
    512x512."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if H % Hkv != 0:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    if block_q is None:
        block_q = 1024 if D <= 64 else 512
    if block_k is None:
        block_k = 1024 if D <= 64 else 512
    bq, bk = _pick_block(S, block_q), _pick_block(T, block_k)
    if bq is None or bk is None:
        raise ValueError(f"seq lens ({S},{T}) not tileable by {LANES}")

    if D % LANES == 0:
        out = _flash_bshd(q.reshape(B, S, H * D),
                          k.reshape(B, T, Hkv * D),
                          v.reshape(B, T, Hkv * D),
                          causal, scale, bq, bk, interpret, H, D)
        return out.reshape(B, S, H, D)

    # fallback: head-major transpose + lane padding (D < 128 models)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    pad = (-D) % LANES
    if pad:
        qb, kb, vb = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
                      for x in (qb, kb, vb))
    out = _flash(qb, kb, vb, causal, scale, bq, bk, interpret)
    if pad:
        out = out[..., :D]
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
