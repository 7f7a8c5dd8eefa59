"""Ring attention — sequence-parallel attention over an ICI ring.

Net-new subsystem vs the reference (SURVEY.md §5.7: FlexFlow has no sequence
parallelism). Design: q/k/v are sequence-sharded over the `seq` mesh axis;
each device computes blockwise (flash-style) attention of its local queries
against the k/v block it currently holds, while k/v blocks rotate around the
ring with `lax.ppermute` — compute overlaps the ICI transfer of the next
block. Online softmax (running max + denominator in fp32) makes the result
exactly equal to full attention.

The lowering is used by OpType.RING_ATTENTION and falls back to plain fused
attention when the sequence axis is unsharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


from flexflow_tpu.parallel.compat import shard_map as _shard_map
from flexflow_tpu.parallel.comm_spec import ring_repeats_kv, ulysses_plan


def _mesh_axis_size(mesh, name: str) -> int:
    if mesh is None or name not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def repeat_kv(k, v, rep: int):
    """Materialize the GQA head repeat (the shared fallback for paths
    that cannot carry unrepeated kv — one definition so every site's
    trigger condition is the only thing that can differ)."""
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def ring_attention_core(q, k, v, *, axis_name: str, n_shards: int, causal: bool,
                        scale: float, vary_axes=()):
    """Per-shard body (inside shard_map). q: (B, s_loc, H, D); k, v:
    (B, s_loc, Hkv, D) — GQA kv rides the ring UNREPEATED (every
    ppermute hop moves 1/rep of the bytes), repeated locally per block;
    device i initially holds sequence block i."""
    B, s_loc, H, D = q.shape
    rep = H // k.shape[2]
    my = lax.axis_index(axis_name)
    NEG = jnp.float32(-1e30)

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, s_loc), NEG, jnp.float32)
    l0 = jnp.zeros((B, H, s_loc), jnp.float32)
    acc0 = jnp.zeros((B, s_loc, H, D), jnp.float32)
    if vary_axes:
        # fori_loop carries must have the same varying-manual-axes type as
        # the body outputs (see jax shard_map vma docs)
        def _vary(t):
            if hasattr(lax, "pcast"):
                return lax.pcast(t, tuple(vary_axes), to="varying")
            if hasattr(lax, "pvary"):
                return lax.pvary(t, tuple(vary_axes))
            return t

        m0, l0, acc0 = (_vary(t) for t in (m0, l0, acc0))
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def body(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (my - i) % n_shards  # which sequence block we hold now
        kb = jnp.repeat(k_blk, rep, axis=2) if rep > 1 else k_blk
        vb = jnp.repeat(v_blk, rep, axis=2) if rep > 1 else v_blk
        logits = jnp.einsum(
            "bshd,bthd->bhst", qf, kb.astype(jnp.float32)
        ) * scale
        if causal:
            q_pos = my * s_loc + lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
            k_pos = src * s_loc + lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
            mask = q_pos >= k_pos
            logits = jnp.where(mask[None, None], logits, NEG)
            pmask = mask[None, None].astype(jnp.float32)
        else:
            pmask = jnp.float32(1.0)
        blk_max = logits.max(axis=-1)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None]) * pmask
        new_l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p, vb.astype(jnp.float32))
        new_acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, new_m, new_l, new_acc)

    _, _, m, l, acc = lax.fori_loop(0, n_shards, body, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_dot_product_attention(q, k, v, *, mesh, causal: bool, scale: float,
                               seq_axis: str = "seq", batch_axis: str = "data",
                               head_axis: str = "model"):
    """q,k,v: (B, S, H, D) global, S sharded over `seq_axis`. Exact
    attention via ring rotation. Falls back to a single local computation
    when the seq axis has size 1."""
    import os

    n = _mesh_axis_size(mesh, seq_axis)
    from flexflow_tpu.ops import jax_ops

    if n == 1:
        return jax_ops.fused_attention(q, k, v, causal=causal, scale=scale,
                                       mesh=mesh)

    ba = batch_axis if _mesh_axis_size(mesh, batch_axis) > 1 else None
    ha = head_axis if _mesh_axis_size(mesh, head_axis) > 1 else None
    # kv arrives UNREPEATED (GQA): head-TP sharding needs the kv head dim
    # divisible too, else repeat up front and lose the hop saving
    # (decision shared with the cost model via parallel.comm_spec)
    h_deg = _mesh_axis_size(mesh, head_axis)
    if ring_repeats_kv(q.shape[2], k.shape[2], h_deg):
        k, v = repeat_kv(k, v, q.shape[2] // k.shape[2])
    spec = P(ba, seq_axis, ha, None)

    # Pallas flash kernel as the per-block ring body (the S_loc×S_loc
    # score tile stays in VMEM); einsum online-softmax fallback otherwise
    from flexflow_tpu.ops.pallas import (
        ring_flash_attention,
        ring_flash_available,
    )

    s_loc = q.shape[1] // n
    force_interp = os.environ.get("FF_TPU_FLASH_INTERPRET") == "1"
    if q.shape[1] % n == 0 and ring_flash_available(
        s_loc, interpret=force_interp
    ):
        jax_ops.LAST_ATTENTION_KERNEL = "ring_pallas_flash"

        def fn(ql, kl, vl):
            return ring_flash_attention(
                ql, kl, vl, axis_name=seq_axis, n_shards=n, causal=causal,
                scale=scale, interpret=force_interp,
            )

        return _shard_map(fn, mesh, (spec, spec, spec), spec,
                          check_vma=False)(q, k, v)

    jax_ops.LAST_ATTENTION_KERNEL = "ring_online_softmax"
    vary_axes = tuple(a for a in (ba, seq_axis, ha) if a is not None)

    def fn(ql, kl, vl):
        return ring_attention_core(
            ql, kl, vl, axis_name=seq_axis, n_shards=n, causal=causal,
            scale=scale, vary_axes=vary_axes,
        )

    # check_vma=False like the pallas ring path: the replication checker
    # cannot type the BACKWARD of the fori_loop carry (zero cotangents
    # enter the transposed scan with no varying annotation and training
    # dies with "mismatched replication types" — caught by hloaudit's
    # train_step lowering, which no test had ever traced for this path)
    return _shard_map(fn, mesh, (spec, spec, spec), spec,
                      check_vma=False)(q, k, v)


def ulysses_dot_product_attention(q, k, v, *, mesh, causal: bool, scale: float,
                                  seq_axis: str = "seq", batch_axis: str = "data",
                                  head_axis: str = "model"):
    """DeepSpeed-Ulysses sequence parallelism: q/k/v arrive seq-sharded;
    ONE all-to-all over the seq axis re-shards heads instead of sequence
    (each device gets ALL positions of H/n heads), full attention runs
    locally, and a second all-to-all restores seq sharding. Lowers the
    OpType.ALL_TO_ALL pattern (parallel_ops.py) into lax.all_to_all pairs.
    Requires heads % seq_degree == 0."""
    n = _mesh_axis_size(mesh, seq_axis)
    from flexflow_tpu.ops import jax_ops

    if n == 1:
        return jax_ops.fused_attention(q, k, v, causal=causal, scale=scale,
                                       mesh=mesh)
    H = q.shape[2]
    Hkv = k.shape[2]
    h_deg = _mesh_axis_size(mesh, head_axis)
    # Exchange-shape decisions (local-head divisibility, GQA repeat —
    # including the ADVICE-r5 rule that Hkv is divided by h_deg only under
    # real head-TP) live in parallel.comm_spec.ulysses_plan, shared with
    # the cost model's pricing so the two sides cannot drift.
    plan = ulysses_plan(H, Hkv, h_deg, n)
    if plan.fallback_to_ring:
        return ring_dot_product_attention(
            q, k, v, mesh=mesh, causal=causal, scale=scale,
            seq_axis=seq_axis, batch_axis=batch_axis, head_axis=head_axis,
        )
    if plan.repeat_kv:
        k, v = repeat_kv(k, v, H // Hkv)
    jax_ops.LAST_ATTENTION_KERNEL = "ulysses_all_to_all"

    ba = batch_axis if _mesh_axis_size(mesh, batch_axis) > 1 else None
    ha = head_axis if plan.head_tp else None
    spec = P(ba, seq_axis, ha, None)

    def fn(ql, kl, vl):
        # (B, s_loc, H, D) -> (B, S, H/n, D): split heads, gather sequence
        ex = lambda t: lax.all_to_all(t, seq_axis, split_axis=2,
                                      concat_axis=1, tiled=True)
        qh, kh, vh = ex(ql), ex(kl), ex(vl)
        out = _dot_attention_local(qh, kh, vh, causal, scale)
        # (B, S, H/n, D) -> (B, s_loc, H, D)
        return lax.all_to_all(out, seq_axis, split_axis=1, concat_axis=2,
                              tiled=True)

    return _shard_map(fn, mesh, (spec, spec, spec), spec,
                      check_vma=False)(q, k, v)


def _dot_attention_local(q, k, v, causal, scale):
    """Per-shard full attention used inside the Ulysses body (flash when
    the local backend supports it)."""
    from flexflow_tpu.ops.jax_ops import _dot_product_attention
    from flexflow_tpu.ops.pallas import (
        flash_attention,
        flash_attention_available,
    )

    if flash_attention_available(q.shape[1], k.shape[1]):
        return flash_attention(q, k, v, causal=causal, scale=scale)
    return _dot_product_attention(q, k, v, causal, scale)


def ring_attention_lowering(attrs, inputs, params, ctx):
    """Lowering for OpType.RING_ATTENTION: same projections as
    MULTIHEAD_ATTENTION, ring core for the attention itself."""
    q_in = inputs[0]
    k_in = inputs[1] if len(inputs) > 1 else q_in
    v_in = inputs[2] if len(inputs) > 2 else k_in
    dt = q_in.dtype
    from flexflow_tpu.ops.jax_ops import attn_out_project, qkv_project

    q = qkv_project(q_in, params["wq"], dt)
    k = qkv_project(k_in, params["wk"], dt)
    v = qkv_project(v_in, params["wv"], dt)
    if attrs.rope:
        # applied at the global (logical) level, before the seq-sharded ring
        # core — positions are global so each shard sees correct angles
        from flexflow_tpu.ops.jax_ops import apply_rope

        q = apply_rope(q, attrs.rope_theta)
        k = apply_rope(k, attrs.rope_theta)
    # GQA kv stays UNREPEATED into the seq-parallel cores: the ring
    # ppermutes (fwd k/v, bwd k/v + dk/dv accumulators) and the Ulysses
    # exchanges then move 1/rep of the bytes; each path repeats locally
    # where its math needs full heads
    seq_attn = (
        ulysses_dot_product_attention
        if getattr(attrs, "seq_mode", "ring") == "ulysses"
        else ring_dot_product_attention
    )
    out = seq_attn(
        q, k, v, mesh=ctx.mesh, causal=attrs.causal, scale=attrs.scale
    )
    y = attn_out_project(out, params["wo"], dt)
    return [y]
