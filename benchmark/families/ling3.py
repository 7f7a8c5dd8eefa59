"""The Ling-3.0-flash family (the language model of `Ling-3.0-flash-VL`:
delta-rule linear-attention (KDA) layers beside latent (MLA) ones by
`layer_group_size`, leading dense layers, then a dropless expert layer
with sigmoid group-limited routing and a shared expert) through the
program's `build_ling3`, cut to one chip's share of a deployment as the
configuration file states: `num_hidden_layers` published layers from
`first_layer` on, `experts_held` of the published router width, a slice
of the vocabulary, weights stored as `torch_dtype` says.

A configuration file holds the published `config.json` keys as they are
run; this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Tuple

from benchmark.reference import ling3 as ref

REQUIRED = ("hidden_size", "num_hidden_layers", "first_layer",
            "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "head_dim", "layer_group_size",
            "short_conv_kernel_size", "kda_lower_bound", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "n_group", "topk_group",
            "score_function", "norm_topk_prob", "routed_scaling_factor",
            "vocab_size", "rms_norm_eps", "experts_held", "published",
            "torch_dtype")
OFF = ("value_norm", "up_proj_norm", "use_nGPT", "scale_router_input",
       "use_kda_lora", "use_mla_nope")


def layer_kinds(cfg: Dict) -> Tuple[str, ...]:
    """"kda" / "mla" for each kept layer, by its PUBLISHED index."""
    first, period = cfg["first_layer"], cfg["layer_group_size"]
    return tuple("mla" if (first + j + 1) % period == 0 else "kda"
                 for j in range(cfg["num_hidden_layers"]))


def dense_layers(cfg: Dict) -> int:
    """How many of the kept layers have the dense MLP (the leading ones)."""
    return cfg["first_k_dense_replace"]


def check(cfg: Dict) -> None:
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    if cfg["q_lora_rank"] is not None:
        raise ValueError("the reference has no low-rank step on q")
    if cfg["score_function"] != "sigmoid":
        raise ValueError("the reference's router scores by sigmoid")
    if not cfg.get("moe_router_enable_expert_bias"):
        raise ValueError("the reference's router selects on a bias")
    if not (cfg.get("kda_safe_gate") and cfg.get("no_kda_lora")
            and cfg.get("linear_silu") and cfg.get("use_qk_norm")):
        raise ValueError("the reference's KDA layer is the bounded gate, "
                         "full-rank, SiLU after the convolution, q/k normed")
    on = [k for k in OFF if cfg.get(k)]
    if on:
        raise ValueError(f"{on} are not built")
    if cfg.get("num_kv_heads_for_linear_attn") or cfg.get(
            "group_norm_size", 1) != 1:
        raise ValueError("a KDA layer has one key/value head and one "
                         "norm a query head")
    if cfg.get("gated_attention_proj_granularity_type") != "head_wise":
        raise ValueError("the latent layer's output gate is head-wise")
    if cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
        raise ValueError("rotary_dim is the latent layer's rope part")
    if cfg["head_dim"] != cfg["v_head_dim"]:
        raise ValueError("a KDA head is head_dim x head_dim")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is untied")
    first, n = cfg["first_layer"], cfg["num_hidden_layers"]
    dense_published = cfg["published"]["first_k_dense_replace"]
    if first + cfg["first_k_dense_replace"] != dense_published:
        raise ValueError("the kept dense layers are the last leading ones")
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        kept = cfg.get(key, [])[first:first + n]
        if any(kept):
            raise ValueError(f"{key} clamps a kept layer ({kept}): the "
                             "clamp is not built")


def program_config(cfg: Dict):
    from flexflow_tpu.models.ling3 import Ling3Config

    check(cfg)
    return Ling3Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_kinds=layer_kinds(cfg), dense_layers=dense_layers(cfg),
        dense_hidden=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        conv_taps=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        n_experts=cfg["published"]["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_shared_expert_intermediate_size"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=tuple(cfg["experts_held"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_ling3` -> `compile()`, one chip, weights drawn
    on the device from the seed and stored as `torch_dtype` says."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.ling3 import build_ling3

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1,
                          weight_dtype=cfg["torch_dtype"]))
    build_ling3(ff, program_config(cfg), batch_size=1, seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _by_name(tree: Dict) -> Dict:
    """The program keys its parameters `<layer name>_<guid>`."""
    return {re.sub(r"_\d+$", "", k): v for k, v in tree.items()}


def reference_weights(trainable: Dict, cfg: Dict) -> ref.Weights:
    """The program's own parameter tree, leaves as stored, as the
    reference's `Weights` (the reference upcasts as it goes)."""
    p = _by_name(trainable)
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        a = p[f"l{i}_attn"]
        if kind == "kda":
            attn = ref.Kda(**{k: a[k] for k in ref.Kda._fields})
        else:
            attn = ref.Mla(wq=a["w_uq"], w_dkv=a["w_dkv"],
                           kv_norm=a["kv_norm"], w_ukv=a["w_ukv"],
                           w_gate=a["w_gate"], wo=a["wo"])
        if i < dense_layers(cfg):
            mlp = ref.Dense(gate=p[f"l{i}_gate"]["kernel"],
                            up=p[f"l{i}_up"]["kernel"],
                            down=p[f"l{i}_down"]["kernel"])
        else:
            m = p[f"l{i}_moe"]
            mlp = ref.Moe(**{k: m[k] for k in ref.Moe._fields})
        layers.append(ref.Layer(
            attn_norm=p[f"l{i}_attn_norm"]["scale"], attn=attn,
            mlp_norm=p[f"l{i}_mlp_norm"]["scale"], mlp=mlp))
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"],
                       head=p["lm_head"]["kernel"])


def reference_arch(cfg: Dict) -> ref.Arch:
    check(cfg)
    lo, hi = cfg["experts_held"]
    return ref.Arch(
        heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        experts_per_tok=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], held_lo=lo, held_hi=hi,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    return functools.partial(ref.logits, arch=reference_arch(cfg))
