"""The Mistral-Small-4 family (`model_type` mistral4: latent attention, a
dropless expert layer with a shared expert in every layer) through the
program's `build_mistral4`, cut to one chip's share of a deployment as the
configuration file states: `experts_held` of the published router width,
a slice of the vocabulary, weights stored as `torch_dtype` says.

A configuration file holds the published `config.json` keys as they are
run; this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import functools
import re
from typing import Dict

from benchmark.reference import mistral4 as ref

REQUIRED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob",
            "routed_scaling_factor", "vocab_size", "rms_norm_eps",
            "rope_parameters", "rope_interleave", "experts_held",
            "published", "torch_dtype")


def check(cfg: Dict) -> None:
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    if cfg.get("first_k_dense_replace", 0):
        raise ValueError("leading dense layers are not built")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not built")
    if not cfg["rope_interleave"]:
        raise ValueError("the reference ropes interleaved pairs")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type") != "yarn":
        raise ValueError("the reference's rope is YaRN")
    if rope["mscale"] != rope["mscale_all_dim"]:
        raise ValueError("program and reference leave cos / sin unscaled")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is untied")


def program_config(cfg: Dict):
    from flexflow_tpu.models.mistral4 import Mistral4Config

    check(cfg)
    rope = cfg["rope_parameters"]
    return Mistral4Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_experts=cfg["published"]["n_routed_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=tuple(cfg["experts_held"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        rope_interleave=bool(cfg["rope_interleave"]),
        llama_4_scaling_beta=float(rope["llama_4_scaling_beta"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_mistral4` -> `compile()`, one chip, weights
    drawn on the device from the seed and stored as `torch_dtype` says
    (`FFConfig.weight_dtype` -> `init_params(weight_dtype=)`)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.mistral4 import build_mistral4

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1,
                          weight_dtype=cfg["torch_dtype"]))
    build_mistral4(ff, program_config(cfg), batch_size=1, seq_len=8)
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
    for i in range(cfg["num_hidden_layers"]):
        a, m = p[f"l{i}_attn"], p[f"l{i}_moe"]
        layers.append(ref.Layer(
            attn_norm=p[f"l{i}_attn_norm"]["scale"], w_dq=a["w_dq"],
            q_norm=a["q_norm"], w_uq=a["w_uq"], w_dkv=a["w_dkv"],
            kv_norm=a["kv_norm"], w_ukv=a["w_ukv"], wo=a["wo"],
            moe_norm=p[f"l{i}_moe_norm"]["scale"], router=m["router"],
            w_gate=m["w_gate"], w_up=m["w_up"], w_down=m["w_down"],
            shared_gate=m["shared_gate"], shared_up=m["shared_up"],
            shared_down=m["shared_down"]))
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"],
                       head=p["lm_head"]["kernel"])


def reference_arch(cfg: Dict) -> ref.Arch:
    check(cfg)
    rope = cfg["rope_parameters"]
    lo, hi = cfg["experts_held"]
    return ref.Arch(
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        experts_per_tok=cfg["num_experts_per_tok"], held_lo=lo, held_hi=hi,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]),
        beta_slow=float(rope["beta_slow"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        llama_4_scaling_beta=float(rope["llama_4_scaling_beta"]))


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    return functools.partial(ref.logits, arch=reference_arch(cfg))
