"""The Granite 4.0-H family (`granitemoehybrid` without routed experts:
Mamba-2 state-space layers beside grouped-query attention without
positions by `layer_types`, a dense SwiGLU in every layer, four published
multipliers, a tied head) through the program's `build_granite4h`.

A configuration file holds the published `config.json` keys as they are
run; this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import functools
import re
from typing import Dict

from benchmark.reference import granite4h as ref

REQUIRED = ("hidden_size", "num_hidden_layers", "layer_types",
            "shared_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
            "mamba_expand", "mamba_conv_bias", "mamba_proj_bias",
            "attention_bias", "position_embedding_type",
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling", "num_local_experts",
            "num_experts_per_tok", "hidden_act", "normalization_function",
            "tie_word_embeddings", "vocab_size", "rms_norm_eps",
            "torch_dtype")


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def check(cfg: Dict) -> None:
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "mamba", "attention"}:
        raise ValueError("layer_types names 'mamba' or 'attention' for "
                         "each of num_hidden_layers layers")
    if cfg["num_local_experts"] or cfg["num_experts_per_tok"]:
        raise ValueError("routed experts are not built: the dense models "
                         "of the family have none")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("the state-space layer has ONE group of B and C")
    if (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            != cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is mamba_expand x "
                         "hidden_size")
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("the mixer's convolution has a bias and its "
                         "projections have none")
    if cfg["attention_bias"]:
        raise ValueError("the attention layers have no bias")
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("the attention layers take no positions")
    if cfg["hidden_act"] != "silu" or cfg[
            "normalization_function"] != "rmsnorm":
        raise ValueError("SwiGLU and RMSNorm are what is built")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the head is the embedding's table")


def program_config(cfg: Dict):
    from flexflow_tpu.models.granite4h import Granite4HConfig

    check(cfg)
    return Granite4HConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        hidden=cfg["shared_intermediate_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_conv=cfg["mamba_d_conv"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_granite4h` -> `compile()`, one chip, weights
    drawn on the device from the seed and stored as `torch_dtype` says."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.granite4h import build_granite4h

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1,
                          weight_dtype=cfg["torch_dtype"]))
    build_granite4h(ff, program_config(cfg), batch_size=1, seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _by_name(tree: Dict) -> Dict:
    """The program keys its parameters `<layer name>_<guid>`."""
    return {re.sub(r"_\d+$", "", k): v for k, v in tree.items()}


def reference_weights(trainable: Dict, cfg: Dict) -> ref.Weights:
    """The program's own parameter tree, leaves as stored, as the
    reference's `Weights` (the reference upcasts as it goes). There is no
    head among them: the program has none either."""
    p = _by_name(trainable)
    if "lm_head" in p:
        raise ValueError("the head has a leaf of its own: it is not tied")
    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        m = p[f"l{i}_mixer"]
        fields = ref.Mamba if kind == "mamba" else ref.Attention
        layers.append(ref.Layer(
            mixer_norm=p[f"l{i}_mixer_norm"]["scale"],
            mixer=fields(**{k: m[k] for k in fields._fields}),
            mlp_norm=p[f"l{i}_mlp_norm"]["scale"],
            gate=p[f"l{i}_gate"]["kernel"], up=p[f"l{i}_up"]["kernel"],
            down=p[f"l{i}_down"]["kernel"]))
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"])


def reference_arch(cfg: Dict) -> ref.Arch:
    check(cfg)
    return ref.Arch(
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    return functools.partial(ref.logits, arch=reference_arch(cfg))
