"""Mistral-Small-4's language model block (`model_type` mistral4): RMSNorm,
multi-head LATENT attention with YaRN rope on interleaved pairs, and a
dropless SwiGLU expert layer with a shared expert in every layer.

A chip of an expert-parallel deployment builds it with `experts_held`:
the router keeps its published width, the chip holds experts
[lo, hi) of it and computes their part of each layer plus the shared
expert (ops/attrs.py ExpertShareAttrs); what the absent experts would
add is left out and the partial sum goes on to the next layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel, Tensor


@dataclasses.dataclass
class Mistral4Config:
    vocab_size: int = 131072
    dim: int = 4096
    layers: int = 36
    heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_experts: int = 128
    experts_per_tok: int = 4
    expert_hidden: int = 2048
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None     # None: all
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_interleave: bool = True
    llama_4_scaling_beta: float = 0.1

    @staticmethod
    def tiny(vocab: int = 128) -> "Mistral4Config":
        """Test-sized: YaRN's blend and the position scale on q are both
        live within a few dozen positions."""
        return Mistral4Config(
            vocab_size=vocab, dim=64, layers=2, heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=16, n_experts=8, experts_per_tok=2, expert_hidden=32,
            rope_factor=8.0, rope_original_max=16)

    def softmax_scale(self) -> float:
        """head^-0.5 times YaRN's mscale(factor, mscale_all_dim)^2 (the
        DeepSeek-V3 convention; cos/sin carry mscale / mscale_all_dim)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_factor) + 1.0
            scale *= m * m
        return scale


def build_mistral4(ff: FFModel, cfg: Mistral4Config, batch_size: int = None,
                   seq_len: int = 2048,
                   dtype: DataType = DataType.BFLOAT16) -> Tensor:
    if cfg.rope_mscale != cfg.rope_mscale_all_dim:
        raise ValueError("rope cos/sin rescaled by mscale / mscale_all_dim "
                         "is not built; the published config has both 1")
    b = batch_size or ff.config.batch_size
    ids = ff.create_tensor((b, seq_len), DataType.INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.dim, dtype=dtype,
                     name="tok_emb")
    for i in range(cfg.layers):
        a = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_attn_norm")
        a = ff.latent_attention(
            a, cfg.dim, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.softmax_scale(), norm_eps=cfg.norm_eps,
            rope_theta=cfg.rope_theta, rope_factor=cfg.rope_factor,
            rope_original_max=cfg.rope_original_max,
            rope_beta_fast=cfg.rope_beta_fast,
            rope_beta_slow=cfg.rope_beta_slow,
            rope_interleave=cfg.rope_interleave,
            q_scale_beta=cfg.llama_4_scaling_beta, name=f"l{i}_attn")
        h = ff.add(h, a, name=f"l{i}_res1")
        m = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_moe_norm")
        m = ff.expert_share(
            m, cfg.n_experts, cfg.experts_per_tok, cfg.expert_hidden,
            held=cfg.experts_held,
            shared_hidden=cfg.n_shared_experts * cfg.expert_hidden,
            norm_topk=cfg.norm_topk_prob,
            routed_scale=cfg.routed_scaling_factor, name=f"l{i}_moe")
        h = ff.add(h, m, name=f"l{i}_res2")
    h = ff.rms_norm(h, eps=cfg.norm_eps, name="final_norm")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits, name="softmax")
