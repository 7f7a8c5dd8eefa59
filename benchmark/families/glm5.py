"""The GLM-5.3-Flash family (`glm5_next_text`: a four-stream residual
mixed by Sinkhorn-normalised coefficients, KDA linear-attention layers
beside sparse latent ones whose indexer keeps the top blocks of pooled
keys, leading dense layers, then sigmoid-routed experts with a shared one)
through the program's `build_glm5`, cut to one chip's share of a
deployment as the configuration file states: `num_hidden_layers`
published layers from `first_layer` on, `experts_held` of the published
router width, a slice of the vocabulary, weights stored as `torch_dtype`
says.

A configuration file holds the published `config.json` keys as they are
run; this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Tuple

from benchmark.reference import glm5 as ref

REQUIRED = ("hidden_size", "num_hidden_layers", "first_layer",
            "first_k_dense_replace", "intermediate_size", "layer_types",
            "mlp_layer_types", "linear_attn_config", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "index_kpool", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "swiglu_limit",
            "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts", "n_group",
            "topk_group", "scoring_func", "topk_method", "norm_topk_prob",
            "routed_scaling_factor", "vocab_size", "rms_norm_eps",
            "experts_held", "published", "torch_dtype", "index_rope_dim",
            "index_rope_theta", "kda_gate_rank")
KINDS = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}


def layer_kinds(cfg: Dict) -> Tuple[str, ...]:
    """"kda" / "dsa" for each kept layer, by its PUBLISHED index."""
    first = cfg["first_layer"]
    return tuple(KINDS[t] for t in
                 cfg["layer_types"][first:first + cfg["num_hidden_layers"]])


def dense_layers(cfg: Dict) -> int:
    """How many of the kept layers have the dense MLP (the leading ones)."""
    return cfg["first_k_dense_replace"]


def check(cfg: Dict) -> None:
    """Refuse, by key, what the program and the reference do not build."""
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    pub = cfg["published"]
    first, n = cfg["first_layer"], cfg["num_hidden_layers"]
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    if len(cfg["layer_types"]) != pub["num_hidden_layers"] or set(
            cfg["layer_types"]) - set(KINDS):
        raise ValueError("layer_types: the published list, linear_attention "
                         "or deepseek_sparse_attention a layer")
    if first + n > pub["num_hidden_layers"]:
        raise ValueError("the kept layers run past the published ones")
    mlp = cfg["mlp_layer_types"][first:first + n]
    dense = cfg["first_k_dense_replace"]
    if mlp != ["dense"] * dense + ["sparse"] * (n - dense) or (
            first + dense != pub["first_k_dense_replace"]):
        raise ValueError("the kept dense layers are the last leading ones, "
                         "as mlp_layer_types has them")
    lin = cfg["linear_attn_config"]
    kda = [i for i, t in enumerate(cfg["layer_types"])
           if t == "linear_attention"]
    if lin["kda_layers"] != kda:
        raise ValueError("linear_attn_config.kda_layers disagrees with "
                         "layer_types")
    if not cfg.get("mhc") or cfg["hc_mult"] < 2:
        raise ValueError("mhc: the residual path is hc_mult streams")
    if cfg["qk_rope_head_dim"] or not cfg.get("mla_use_nope"):
        raise ValueError("the sparse latent layer has no rope part")
    if cfg.get("qk_head_dim", cfg["qk_nope_head_dim"]) != cfg[
            "qk_nope_head_dim"]:
        raise ValueError("qk_head_dim is the part without rope")
    if cfg["q_lora_rank"] is None:
        raise ValueError("the indexer reads the query's low-rank step")
    if not (cfg.get("index_kpool_compress")
            and cfg.get("index_kpool_always_select_tail")
            and cfg.get("indexer_rope_interleave")):
        raise ValueError("the indexer: pooled keys are what is cached, the "
                         "tail block is always kept, rope on interleaved "
                         "pairs")
    if set(cfg.get("indexer_types", ["full"])) != {"full"}:
        raise ValueError("indexer_types: every sparse layer has its own")
    if cfg["index_topk"] % cfg["index_kpool"]:
        raise ValueError("index_topk counts tokens, whole blocks of them")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("the router scores by sigmoid and selects on a "
                         "bias")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the router has no groups")
    if cfg["n_shared_experts"] != 1:
        raise ValueError("one shared expert of moe_intermediate_size")
    if cfg.get("hidden_act") != "silu" or cfg.get("attention_bias"):
        raise ValueError("SwiGLU under silu, no attention bias")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the head is untied")


def program_config(cfg: Dict):
    from flexflow_tpu.models.glm5 import Glm5Config

    check(cfg)
    lin = cfg["linear_attn_config"]
    return Glm5Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_kinds=layer_kinds(cfg), dense_layers=dense_layers(cfg),
        dense_hidden=cfg["intermediate_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_taps=lin["short_conv_kernel_size"],
        kda_lower_bound=float(lin["gate_lower_bound"]),
        kda_gate_rank=cfg["kda_gate_rank"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], index_heads=cfg["index_n_heads"],
        index_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        index_pool=cfg["index_kpool"],
        index_rope_dim=cfg["index_rope_dim"],
        index_rope_theta=float(cfg["index_rope_theta"]),
        hc_streams=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        swiglu_limit=float(cfg["swiglu_limit"]),
        n_experts=cfg["published"]["n_routed_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=tuple(cfg["experts_held"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_glm5` -> `compile()`, one chip, weights drawn
    on the device from the seed and stored as `torch_dtype` says."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.glm5 import build_glm5

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1,
                          weight_dtype=cfg["torch_dtype"]))
    build_glm5(ff, program_config(cfg), batch_size=1, seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _by_name(tree: Dict) -> Dict:
    """The program keys its parameters `<layer name>_<guid>`."""
    return {re.sub(r"_\d+$", "", k): v for k, v in tree.items()}


def reference_weights(trainable: Dict, cfg: Dict) -> ref.Weights:
    """The program's own parameter tree, leaves as stored, as the
    reference's `Weights` (the reference upcasts as it goes)."""
    p = _by_name(trainable)

    def hc(name):
        return ref.Hc(**{k: p[name][k] for k in ref.Hc._fields})

    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        a = p[f"l{i}_attn"]
        kind_of = ref.Kda if kind == "kda" else ref.Dsa
        attn = kind_of(**{k: a[k] for k in kind_of._fields})
        if i < dense_layers(cfg):
            mlp = ref.Dense(gate=p[f"l{i}_gate"]["kernel"],
                            up=p[f"l{i}_up"]["kernel"],
                            down=p[f"l{i}_down"]["kernel"])
        else:
            m = p[f"l{i}_moe"]
            mlp = ref.Moe(**{k: m[k] for k in ref.Moe._fields})
        layers.append(ref.Layer(
            attn_hc=hc(f"l{i}_attn_hc_pre"),
            attn_norm=p[f"l{i}_attn_norm"]["scale"], attn=attn,
            mlp_hc=hc(f"l{i}_mlp_hc_pre"),
            mlp_norm=p[f"l{i}_mlp_norm"]["scale"], mlp=mlp))
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"],
                       head=p["lm_head"]["kernel"])


def reference_arch(cfg: Dict, **controls) -> ref.Arch:
    check(cfg)
    lin = cfg["linear_attn_config"]
    lo, hi = cfg["experts_held"]
    return ref.Arch(
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_lower_bound=float(lin["gate_lower_bound"]),
        heads=cfg["num_attention_heads"],
        qk_head_dim=cfg["qk_nope_head_dim"], index_topk=cfg["index_topk"],
        index_pool=cfg["index_kpool"], index_rope_dim=cfg["index_rope_dim"],
        index_rope_theta=float(cfg["index_rope_theta"]),
        hc_streams=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        swiglu_limit=float(cfg["swiglu_limit"]),
        experts_per_tok=cfg["num_experts_per_tok"], held_lo=lo, held_hi=hi,
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]), **controls)


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    return functools.partial(ref.logits, arch=reference_arch(cfg))
