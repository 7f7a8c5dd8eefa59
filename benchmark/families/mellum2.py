"""The Mellum-2 family (`model_type` mellum: grouped-query attention whose
layers are sliding-window or full by `layer_types`, each type with its own
rope, and a dropless expert layer in every block, no shared expert)
through the program's `build_mellum2`, cut to one chip's share of a
deployment as the configuration file states: the first
`num_hidden_layers` entries of `layer_types`, `experts_held` of the
published router width, a slice of the vocabulary, weights stored as
`torch_dtype` says.

A configuration file holds the published `config.json` keys as they are
run; this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import functools
import re
from typing import Dict

from benchmark.reference import mellum2 as ref

REQUIRED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "layer_types",
            "mlp_layer_types", "sliding_window", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "vocab_size", "rms_norm_eps",
            "rope_parameters", "experts_held", "published", "torch_dtype")


def check(cfg: Dict) -> None:
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    n = cfg["num_hidden_layers"]
    if len(cfg["layer_types"]) < n or len(cfg["mlp_layer_types"]) < n:
        raise ValueError("layer_types is shorter than the depth")
    if set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("only sparse MLP layers are built")
    if not cfg.get("use_sliding_window", True):
        raise ValueError("use_sliding_window false is not built")
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not built")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is untied")
    rope = cfg["rope_parameters"]
    if rope["sliding_attention"].get("rope_type") != "default":
        raise ValueError("the sliding layers' rope is the plain one")
    if rope["full_attention"].get("rope_type") != "yarn":
        raise ValueError("the full layers' rope is YaRN")


def layer_types(cfg: Dict):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def program_config(cfg: Dict):
    from flexflow_tpu.models.mellum2 import Mellum2Config

    check(cfg)
    full = cfg["rope_parameters"]["full_attention"]
    return Mellum2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=layer_types(cfg),
        sliding_window=int(cfg["sliding_window"]),
        n_experts=cfg["published"]["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        experts_held=tuple(cfg["experts_held"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        sliding_rope_theta=float(
            cfg["rope_parameters"]["sliding_attention"]["rope_theta"]),
        full_rope_theta=float(full["rope_theta"]),
        full_rope_factor=float(full["factor"]),
        full_rope_original_max=int(full["original_max_position_embeddings"]),
        full_rope_beta_fast=float(full["beta_fast"]),
        full_rope_beta_slow=float(full["beta_slow"]),
        full_rope_attention_factor=float(full["attention_factor"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_mellum2` -> `compile()`, one chip, weights
    drawn on the device from the seed and stored as `torch_dtype` says
    (`FFConfig.weight_dtype` -> `init_params(weight_dtype=)`)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.mellum2 import build_mellum2

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1,
                          weight_dtype=cfg["torch_dtype"]))
    build_mellum2(ff, program_config(cfg), batch_size=1, seq_len=8)
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
            attn_norm=p[f"l{i}_attn_norm"]["scale"], wq=a["wq"],
            wk=a["wk"], wv=a["wv"], wo=a["wo"],
            moe_norm=p[f"l{i}_moe_norm"]["scale"], router=m["router"],
            w_gate=m["w_gate"], w_up=m["w_up"], w_down=m["w_down"]))
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"],
                       head=p["lm_head"]["kernel"])


def reference_arch(cfg: Dict) -> ref.Arch:
    check(cfg)
    rope = cfg["rope_parameters"]
    full = rope["full_attention"]
    lo, hi = cfg["experts_held"]
    return ref.Arch(
        layer_sliding=tuple(t == "sliding_attention"
                            for t in layer_types(cfg)),
        sliding_window=int(cfg["sliding_window"]),
        experts_per_tok=cfg["num_experts_per_tok"], held_lo=lo, held_hi=hi,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        sliding_theta=float(rope["sliding_attention"]["rope_theta"]),
        full_theta=float(full["rope_theta"]),
        full_factor=float(full["factor"]),
        full_original_max=int(full["original_max_position_embeddings"]),
        full_beta_fast=float(full["beta_fast"]),
        full_beta_slow=float(full["beta_slow"]),
        full_attention_factor=float(full["attention_factor"]))


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    return functools.partial(ref.logits, arch=reference_arch(cfg))
