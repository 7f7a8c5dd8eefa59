"""GLM-5.3-Flash's language model block (`glm5_next_text`): a residual
path of FOUR streams mixed around every block by Sinkhorn-normalised
coefficients (`hyper_connection`), delta-rule LINEAR attention layers
(KDA, the decay and output gates through a low-rank step) beside, every
fourth layer, a SPARSE latent attention layer without rope whose learned
indexer chooses the blocks of four tokens a query attends to; a dense
SwiGLU in the leading layers and a dropless expert layer (sigmoid scores,
a selection bias, one shared expert) in the rest, every SwiGLU clamped at
`swiglu_limit`.

Three kinds of memory live side by side in one graph: a KDA layer keeps a
fixed-size state a sequence, the sparse layer a latent row a token AND a
pooled indexer key a block of four tokens on the same pages. A chip of an
expert-parallel deployment builds it with `experts_held`, as
`build_mistral4` and `build_ling3` do.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel, Tensor
from flexflow_tpu.runtime.initializer import (
    ConstantInitializer,
    UniformInitializer,
)


@dataclasses.dataclass
class Glm5Config:
    vocab_size: int = 154880
    dim: int = 4096
    # "kda" / "dsa" a layer, by the published `layer_types`
    layer_kinds: Tuple[str, ...] = ()
    dense_layers: int = 3           # leading layers with the dense MLP
    dense_hidden: int = 12288
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_taps: int = 4
    kda_lower_bound: float = -5.0
    kda_gate_rank: Optional[int] = 128
    heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 256
    qk_rope_head_dim: int = 0
    v_head_dim: int = 256
    index_heads: int = 32
    index_dim: int = 128
    index_topk: int = 2048
    index_pool: int = 4
    index_rope_dim: int = 64
    index_rope_theta: float = 1e6
    hc_streams: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # seeded draws of a mixing's parameters (the paper starts alpha at
    # 0.01 and so does this; b and phi are drawn WIDE so that a lost or
    # misplaced mixing cannot hide: b uniform within `hc_bias_range` of
    # 0, phi within `hc_phi_scale` / sqrt(n C) so that alpha x~ phi has
    # a standard deviation of 0.01 x 64 / sqrt(3) = 0.37 whatever the
    # width)
    hc_alpha: float = 0.01
    hc_bias_range: float = 1.5
    hc_phi_scale: float = 64.0
    swiglu_limit: float = 10.0
    n_experts: int = 288
    experts_per_tok: int = 8
    expert_hidden: int = 2048
    shared_hidden: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: Optional[Tuple[int, int]] = None     # None: all
    select_bias_range: float = 0.003    # Ling3Config says why it is small
    norm_eps: float = 1e-5

    @staticmethod
    def tiny(vocab: int = 128) -> "Glm5Config":
        """Test-sized: a dense KDA layer, an expert SPARSE layer that keeps
        4 blocks of 4 tokens (16 tokens a query), an expert KDA layer; 8
        experts, 2 a token; heads of 16."""
        return Glm5Config(
            vocab_size=vocab, dim=64, layer_kinds=("kda", "dsa", "kda"),
            dense_layers=1, dense_hidden=96, kda_heads=4, kda_head_dim=16,
            kda_gate_rank=8, heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, v_head_dim=8, index_heads=2, index_dim=16,
            index_topk=16, index_rope_dim=8, index_rope_theta=10000.0,
            n_experts=8, experts_per_tok=2, expert_hidden=32,
            shared_hidden=32, swiglu_limit=10.0)


def build_glm5(ff: FFModel, cfg: Glm5Config, batch_size: int = None,
               seq_len: int = 2048,
               dtype: DataType = DataType.BFLOAT16) -> Tensor:
    unknown = set(cfg.layer_kinds) - {"kda", "dsa"}
    if unknown or not cfg.layer_kinds:
        raise ValueError(f"layer_kinds {cfg.layer_kinds}: 'kda' or 'dsa' "
                         "a layer")
    n = cfg.hc_streams
    phi_lim = cfg.hc_phi_scale / (n * cfg.dim) ** 0.5
    hc = dict(streams=n, sinkhorn_iters=cfg.hc_sinkhorn_iters,
              eps=cfg.hc_eps, norm_eps=cfg.norm_eps)
    hc_init = {"phi": UniformInitializer(-phi_lim, phi_lim),
               "b": UniformInitializer(-cfg.hc_bias_range,
                                       cfg.hc_bias_range),
               "alpha": ConstantInitializer(cfg.hc_alpha)}
    bias_init = UniformInitializer(-cfg.select_bias_range,
                                   cfg.select_bias_range)
    L = cfg.swiglu_limit

    def block(x, name, fn):
        """X -> Hres X + Hpost^T F(Hpre X), F with its own pre-norm."""
        h, coef = ff.hyper_connection("pre", x, initializers=hc_init,
                                      name=f"{name}_hc_pre", **hc)
        y = fn(ff.rms_norm(h, eps=cfg.norm_eps, name=f"{name}_norm"))
        return ff.hyper_connection("post", x, coef, y,
                                   name=f"{name}_hc_post", **hc)

    def attention(i, kind):
        if kind == "kda":
            return lambda a: ff.kda_attention(
                a, cfg.dim, cfg.kda_heads, cfg.kda_head_dim,
                conv_taps=cfg.conv_taps, lower_bound=cfg.kda_lower_bound,
                norm_eps=cfg.norm_eps, gate_rank=cfg.kda_gate_rank,
                name=f"l{i}_attn")
        return lambda a: ff.latent_attention(
            a, cfg.dim, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
            norm_eps=cfg.norm_eps, index_heads=cfg.index_heads,
            index_dim=cfg.index_dim, index_topk=cfg.index_topk,
            index_pool=cfg.index_pool, index_rope_dim=cfg.index_rope_dim,
            index_rope_theta=cfg.index_rope_theta, name=f"l{i}_attn")

    def mlp(i):
        if i >= cfg.dense_layers:
            return lambda m: ff.expert_share(
                m, cfg.n_experts, cfg.experts_per_tok, cfg.expert_hidden,
                held=cfg.experts_held, shared_hidden=cfg.shared_hidden,
                norm_topk=cfg.norm_topk_prob,
                routed_scale=cfg.routed_scaling_factor, score="sigmoid",
                select_bias=True, bias_initializer=bias_init,
                swiglu_limit=L, name=f"l{i}_moe")

        def dense(m):
            gate = ff.dense(m, cfg.dense_hidden, use_bias=False,
                            name=f"l{i}_gate")
            up = ff.dense(m, cfg.dense_hidden, use_bias=False,
                          name=f"l{i}_up")
            if L:
                gate = ff.scalar_min(gate, L, name=f"l{i}_gate_clamp")
                up = ff.clip(up, L, name=f"l{i}_up_clamp")
            m = ff.multiply(ff.silu(gate, name=f"l{i}_silu"), up,
                            name=f"l{i}_gxu")
            return ff.dense(m, cfg.dim, use_bias=False, name=f"l{i}_down")

        return dense

    b = batch_size or ff.config.batch_size
    ids = ff.create_tensor((b, seq_len), DataType.INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.dim, dtype=dtype,
                     name="tok_emb")
    x = ff.hyper_connection("expand", h, name="hc_expand", **hc)
    for i, kind in enumerate(cfg.layer_kinds):
        x = block(x, f"l{i}_attn", attention(i, kind))
        x = block(x, f"l{i}_mlp", mlp(i))
    h = ff.hyper_connection("sum", x, name="hc_sum", **hc)
    h = ff.rms_norm(h, eps=cfg.norm_eps, name="final_norm")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits, name="softmax")
