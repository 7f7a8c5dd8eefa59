"""The Mellum-2 language model block (`model_type` mellum): RMSNorm, GQA
attention whose layers are sliding-window or full BY `layer_types`, each
type with its own rope (plain for the window layers, YaRN for the full
ones), and a dropless SwiGLU expert layer in every block, no shared
expert.

A chip of an expert-parallel deployment builds it with `experts_held`
(models/mistral4.py tells how the share is cut). Served paged, the window
layers' K/V lives in a class of pages of its own that holds a window and
a chunk a request (paged/scheduler.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel, Tensor, _glorot
from flexflow_tpu.runtime.initializer import NormInitializer

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class Mellum2Config:
    vocab_size: int = 98304
    dim: int = 2304
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    # one entry a layer; its length is the depth
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    sliding_window: int = 1024
    n_experts: int = 64
    experts_per_tok: int = 8
    expert_hidden: int = 896
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # None: all
    norm_eps: float = 1e-6
    # rope_parameters.sliding_attention: the plain rope
    sliding_rope_theta: float = 500000.0
    # rope_parameters.full_attention: YaRN
    full_rope_theta: float = 500000.0
    full_rope_factor: float = 16.0
    full_rope_original_max: int = 8192
    full_rope_beta_fast: float = 32.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.2772588722239782

    @staticmethod
    def tiny(vocab: int = 128, periods: int = 1) -> "Mellum2Config":
        """Test-sized: two window layers and two full ones a period,
        a window of 16 rows, YaRN's ramp live within a few dozen
        positions. Head size 128 is the published one (the ragged
        kernel's lane tile)."""
        return Mellum2Config(
            vocab_size=vocab, dim=128, heads=4, kv_heads=2, head_dim=128,
            layer_types=(SLIDING, SLIDING, FULL, FULL) * periods,
            sliding_window=16, n_experts=8, experts_per_tok=2,
            expert_hidden=64, full_rope_factor=4.0,
            full_rope_original_max=16)

    def full_rope_scaling(self) -> Tuple[float, int, float, float, float]:
        return (self.full_rope_factor, self.full_rope_original_max,
                self.full_rope_beta_fast, self.full_rope_beta_slow,
                self.full_rope_attention_factor)


def build_mellum2(ff: FFModel, cfg: Mellum2Config, batch_size: int = None,
                  seq_len: int = 2048,
                  dtype: DataType = DataType.BFLOAT16) -> Tensor:
    """The weights are DRAWN so that a deep stack of them still tells its
    tokens apart: token embeddings at unit variance, every matrix
    Glorot-uniform over its own fans, and the two matrices that write to
    the residual stream (attention's Wo, an expert's Wd) scaled by
    1 / sqrt(2 x depth), the usual scaled initialisation of residual
    layers. At the program's defaults (embeddings at 0.02) the first
    layer's averaged values swamp every token's own vector, all tokens of
    a launch then choose the same experts, and a chunk reaches 6 of 16
    held experts where a trained model reaches all (PERF.md section 6,
    PR 36)."""
    unknown = set(cfg.layer_types) - {SLIDING, FULL}
    if unknown:
        raise ValueError(f"layer_types holds {sorted(unknown)}; built are "
                         f"{SLIDING!r} and {FULL!r}")
    b = batch_size or ff.config.batch_size
    ids = ff.create_tensor((b, seq_len), DataType.INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.dim, dtype=dtype,
                     kernel_initializer=NormInitializer(0.0, 1.0),
                     name="tok_emb")
    out_scale = (2.0 * len(cfg.layer_types)) ** -0.5
    width, kv_width = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    for i, kind in enumerate(cfg.layer_types):
        a = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_attn_norm")
        if kind == SLIDING:
            rope = dict(rope_theta=cfg.sliding_rope_theta,
                        window=cfg.sliding_window)
        else:
            rope = dict(rope_theta=cfg.full_rope_theta,
                        rope_scaling=cfg.full_rope_scaling())
        a = ff.multihead_attention(
            a, a, a, cfg.dim, cfg.heads, kdim=cfg.heads * cfg.head_dim,
            bias=False, causal=True, kv_heads=cfg.kv_heads, rope=True,
            name=f"l{i}_attn", **rope)
        ff._record_init(a.node, wq=_glorot(cfg.dim, width),
                        wk=_glorot(cfg.dim, kv_width),
                        wv=_glorot(cfg.dim, kv_width),
                        wo=_glorot(width, cfg.dim, out_scale))
        h = ff.add(h, a, name=f"l{i}_res1")
        m = ff.rms_norm(h, eps=cfg.norm_eps, name=f"l{i}_moe_norm")
        m = ff.expert_share(
            m, cfg.n_experts, cfg.experts_per_tok, cfg.expert_hidden,
            held=cfg.experts_held, shared_hidden=0,
            norm_topk=cfg.norm_topk_prob, name=f"l{i}_moe")
        init = _glorot(cfg.dim, cfg.expert_hidden)
        ff._record_init(m.node, w_gate=init, w_up=init,
                        w_down=_glorot(cfg.expert_hidden, cfg.dim, out_scale))
        h = ff.add(h, m, name=f"l{i}_res2")
    h = ff.rms_norm(h, eps=cfg.norm_eps, name="final_norm")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits, name="softmax")
