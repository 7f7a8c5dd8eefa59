"""Ling-3.0-flash's language model block (the text part of
`Ling-3.0-flash-VL`): RMSNorm, then a delta-rule LINEAR attention layer
(KDA) or, every `layer_group_size`-th layer, multi-head LATENT attention
with a head-wise output gate; then a dense SwiGLU in the leading layers
and a dropless expert layer (sigmoid scores, a selection bias,
group-limited routing, one shared expert) in the rest.

Two kinds of memory live side by side in one graph: a KDA layer keeps a
fixed-size state a sequence, the latent layer a row a token on pages. A
chip of an expert-parallel deployment builds it with `experts_held`, as
`build_mistral4` does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel, Tensor
from flexflow_tpu.runtime.initializer import UniformInitializer


@dataclasses.dataclass
class Ling3Config:
    vocab_size: int = 157184
    dim: int = 2560
    # "kda" / "mla" a layer: published layer i is latent iff
    # (i + 1) % layer_group_size == 0 (6), linear otherwise
    layer_kinds: Tuple[str, ...] = ()
    dense_layers: int = 2           # leading layers with the dense MLP
    dense_hidden: int = 6144
    heads: int = 32
    kda_head_dim: int = 128
    conv_taps: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    n_experts: int = 512
    experts_per_tok: int = 8
    expert_hidden: int = 768
    shared_hidden: int = 768
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: Optional[Tuple[int, int]] = None     # None: all
    # a seeded draw of the router's selection bias is uniform within this
    # of zero: not zero, so that selection and weighting differ (it changes
    # about a fifth of the tokens' choices at 512 sigmoid-scored experts),
    # and small, as a bias that balances the load is: a token's top scores
    # lie 0.005-0.01 apart, and a bias of 0.1 made every token choose the
    # same half of the experts (PERF.md section 6, PR 44)
    select_bias_range: float = 0.003
    norm_eps: float = 1e-6

    @staticmethod
    def tiny(vocab: int = 128) -> "Ling3Config":
        """Test-sized: a dense KDA layer, an expert KDA layer, an expert
        latent layer; 2 groups of 4 experts, the better group open, 2 a
        token; heads of 16."""
        return Ling3Config(
            vocab_size=vocab, dim=64, layer_kinds=("kda", "kda", "mla"),
            dense_layers=1, dense_hidden=96, heads=4, kda_head_dim=16,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=16, rope_theta=10000.0, n_experts=8,
            experts_per_tok=2, expert_hidden=32, shared_hidden=32,
            n_group=2, topk_group=1)


def build_ling3(ff: FFModel, cfg: Ling3Config, batch_size: int = None,
                seq_len: int = 2048,
                dtype: DataType = DataType.BFLOAT16) -> Tensor:
    unknown = set(cfg.layer_kinds) - {"kda", "mla"}
    if unknown or not cfg.layer_kinds:
        raise ValueError(f"layer_kinds {cfg.layer_kinds}: 'kda' or 'mla' "
                         "a layer")
    bias_init = UniformInitializer(-cfg.select_bias_range,
                                   cfg.select_bias_range)
    b = batch_size or ff.config.batch_size
    ids = ff.create_tensor((b, seq_len), DataType.INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.dim, dtype=dtype,
                     name="tok_emb")
    for i, kind in enumerate(cfg.layer_kinds):
        a = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_attn_norm")
        if kind == "kda":
            a = ff.kda_attention(
                a, cfg.dim, cfg.heads, cfg.kda_head_dim,
                conv_taps=cfg.conv_taps, lower_bound=cfg.kda_lower_bound,
                norm_eps=cfg.norm_eps, name=f"l{i}_attn")
        else:
            a = ff.latent_attention(
                a, cfg.dim, cfg.heads, None, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                rope_interleave=False, out_gate=True, name=f"l{i}_attn")
        h = ff.add(h, a, name=f"l{i}_res1")
        m = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_mlp_norm")
        if i < cfg.dense_layers:
            gate = ff.dense(m, cfg.dense_hidden, use_bias=False,
                            name=f"l{i}_gate")
            up = ff.dense(m, cfg.dense_hidden, use_bias=False,
                          name=f"l{i}_up")
            m = ff.multiply(ff.silu(gate, name=f"l{i}_silu"), up,
                            name=f"l{i}_gxu")
            m = ff.dense(m, cfg.dim, use_bias=False, name=f"l{i}_down")
        else:
            m = ff.expert_share(
                m, cfg.n_experts, cfg.experts_per_tok, cfg.expert_hidden,
                held=cfg.experts_held, shared_hidden=cfg.shared_hidden,
                norm_topk=cfg.norm_topk_prob,
                routed_scale=cfg.routed_scaling_factor, score="sigmoid",
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                select_bias=True, bias_initializer=bias_init,
                name=f"l{i}_moe")
        h = ff.add(h, m, name=f"l{i}_res2")
    h = ff.rms_norm(h, eps=cfg.norm_eps, name="final_norm")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits, name="softmax")
