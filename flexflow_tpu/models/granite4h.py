"""Granite 4.0-H's block (`granitemoehybrid`, the dense Micro model):
RMSNorm, then a Mamba-2 STATE-SPACE mixer or, every tenth layer, grouped-
query attention WITHOUT positions and with a published score scale; then a
dense SwiGLU; four published scalars (the embedding's, the residual's, the
scores' and the logits' multipliers) and a head that IS the embedding's
table.

    h = embedding_multiplier * E[ids]
    h += residual_multiplier * Mixer(RMSNorm(h))
    h += residual_multiplier * SwiGLU(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling

Two kinds of memory live side by side in one graph, as in `build_ling3`:
a Mamba layer keeps a fixed-size state a sequence, the attention layer
K/V rows on pages (heads of 64: paged/attention.py "HEADS OF 64").
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel, Tensor, _glorot
from flexflow_tpu.runtime.initializer import (
    ConstantInitializer,
    NormInitializer,
)

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass
class Granite4HConfig:
    vocab_size: int = 100352
    dim: int = 2048
    # "mamba" / "attention" a layer, as published (`layer_types`)
    layer_types: Tuple[str, ...] = ()
    hidden: int = 8192              # the SwiGLU's (`shared_intermediate_size`)
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5

    @staticmethod
    def tiny(vocab: int = 128) -> "Granite4HConfig":
        """Test-sized: three Mamba layers around one attention layer; 8
        state-space heads of 16 with a state of 128, 4 query heads over 2
        kv heads of 64 (the packed path of the ragged kernel), the four
        multipliers as published."""
        return Granite4HConfig(
            vocab_size=vocab, dim=64,
            layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA), hidden=96,
            heads=4, kv_heads=2, head_dim=64, mamba_heads=8,
            mamba_head_dim=16, mamba_state=128)


def build_granite4h(ff: FFModel, cfg: Granite4HConfig,
                    batch_size: int = None, seq_len: int = 2048,
                    dtype: DataType = DataType.BFLOAT16) -> Tensor:
    """The head has no leaf: it multiplies by the embedding's. With
    RANDOM weights that tie has a consequence a trained model does not
    show: the stream keeps a share of the token's own embedding to the
    end, and its product with that same row of the table is the token's
    own logit. At a deviation of 0.02 (the family's initializer range)
    the own logit stands 7 standard deviations over its row at the
    published width: the model answers every token with itself, in any
    precision, and a comparison with the reference would judge nothing.
    So the table is drawn at 0.02 / `embedding_multiplier`: the stream
    starts at 0.02, the layers (which see it through RMSNorm, at any
    scale) write 1-2 over it, and the own logit is half a deviation
    (0.57 at the median on the chip, PERF.md section 6, PR 51). A table
    that small would leave a row's logits deviating by 0.009, its
    probabilities by a percent of each other, which bfloat16
    probabilities cannot tell apart (served tokens then lay 0.6
    deviations under the reference's argmax where bfloat16's rounding
    explains 0.05), so the FINAL norm's scale is drawn at
    `logits_scaling` / (the table's deviation x sqrt(dim)), about 106: a
    row's logits deviate by 1, as a trained model's do. Every matrix is
    Glorot-uniform over its own fans."""
    unknown = set(cfg.layer_types) - {MAMBA, ATTENTION}
    if unknown or not cfg.layer_types:
        raise ValueError(f"layer_types {cfg.layer_types}: {MAMBA!r} or "
                         f"{ATTENTION!r} a layer")
    b = batch_size or ff.config.batch_size
    ids = ff.create_tensor((b, seq_len), DataType.INT32, name="input_ids")
    table_std = 0.02 / cfg.embedding_multiplier
    h, table = ff.embedding(
        ids, cfg.vocab_size, cfg.dim, dtype=dtype, name="tok_emb",
        kernel_initializer=NormInitializer(0.0, table_std), emit_table=True)
    h = ff.scalar_multiply(h, cfg.embedding_multiplier, name="emb_scale")
    width, kv_width = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim

    def residual(h, branch, name):
        return ff.add(h, ff.scalar_multiply(branch, cfg.residual_multiplier,
                                            name=name + "_scale"), name=name)

    for i, kind in enumerate(cfg.layer_types):
        a = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_mixer_norm")
        if kind == MAMBA:
            a = ff.mamba2(a, cfg.dim, cfg.mamba_heads, cfg.mamba_head_dim,
                          cfg.mamba_state, conv_taps=cfg.mamba_conv,
                          norm_eps=cfg.norm_eps, name=f"l{i}_mixer")
        else:
            a = ff.multihead_attention(
                a, a, a, cfg.dim, cfg.heads, kdim=width, bias=False,
                causal=True, kv_heads=cfg.kv_heads, rope=False,
                softmax_scale=cfg.attention_multiplier, name=f"l{i}_mixer")
            ff._record_init(a.node, wq=_glorot(cfg.dim, width),
                            wk=_glorot(cfg.dim, kv_width),
                            wv=_glorot(cfg.dim, kv_width),
                            wo=_glorot(width, cfg.dim))
        h = residual(h, a, f"l{i}_res1")
        m = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_mlp_norm")
        gate = ff.dense(m, cfg.hidden, use_bias=False, name=f"l{i}_gate")
        up = ff.dense(m, cfg.hidden, use_bias=False, name=f"l{i}_up")
        m = ff.multiply(ff.silu(gate, name=f"l{i}_silu"), up,
                        name=f"l{i}_gxu")
        m = ff.dense(m, cfg.dim, use_bias=False, name=f"l{i}_down")
        h = residual(h, m, f"l{i}_res2")
    h = ff.rms_norm(h, eps=cfg.norm_eps, name="final_norm")
    ff._record_init(h.node, scale=ConstantInitializer(
        cfg.logits_scaling / (table_std * cfg.dim ** 0.5)))
    logits = ff.tied_head(h, table, scale=1.0 / cfg.logits_scaling,
                          name="lm_head")
    return ff.softmax(logits, name="softmax")
