"""The Mistral family (Mistral-7B-v0.3's block: RMSNorm, grouped-query
attention with rotary embeddings, SwiGLU, untied head) through the program's
`build_llama`, which has the same block equations.

A configuration file holds the published `config.json` keys as they are run;
this module is the only place that maps them to the program's names.
"""

from __future__ import annotations

import re
from typing import Dict

from benchmark.reference import mistral as ref

REQUIRED = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps")


def check(cfg: Dict) -> None:
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("build_llama takes head_dim = hidden / heads")
    if cfg.get("sliding_window") is not None:
        raise ValueError("a sliding window is not built by build_llama")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("build_llama's head is untied")


def program_config(cfg: Dict):
    from flexflow_tpu.models.llama import LlamaConfig

    check(cfg)
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hidden=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def build_server_model(cfg: Dict, seed: int):
    """`FFModel` -> `build_llama` -> `compile()`, one chip, weights drawn
    on the device from the seed (the program's own `init_params`)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.models.llama import build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_llama(ff, program_config(cfg), batch_size=1, seq_len=8)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def build_trainer_model(cfg: Dict, seed: int):
    """The trainer as `cfg["trainer"]` states it: mesh, hand-written
    tensor-parallel strategy, rematerialisation, Adam's state type."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.llama import build_llama, llama_tp_strategy

    t = cfg["trainer"]
    lcfg = program_config(cfg)
    mesh = dict(t["mesh"]) if t.get("mesh") else None
    chips = 1
    for v in (mesh or {}).values():
        chips *= int(v)
    ff = FFModel(FFConfig(batch_size=t["batch"], seed=seed,
                          num_devices=chips, mesh_shape=mesh,
                          remat=t.get("remat")))
    build_llama(ff, lcfg, seq_len=t["seq"])
    if t["strategy"] not in ("llama_tp_strategy", "none"):
        raise ValueError(f"unknown strategy {t['strategy']!r}")
    strategy = (llama_tp_strategy(lcfg)
                if t["strategy"] == "llama_tp_strategy" else None)
    ff.compile(optimizer=AdamOptimizer(lr=t["lr"],
                                       state_dtype=t["adam_state_dtype"]),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=strategy)
    return ff


def _by_name(tree: Dict) -> Dict:
    """The program keys its parameters `<layer name>_<guid>`."""
    return {re.sub(r"_\d+$", "", k): v for k, v in tree.items()}


def reference_weights(trainable: Dict, cfg: Dict) -> ref.Weights:
    """The program's own parameter tree, as the reference's `Weights`."""
    p = _by_name(trainable)
    layers = [ref.Layer(
        attn_norm=p[f"l{i}_attn_norm"]["scale"],
        wq=p[f"l{i}_attn"]["wq"], wk=p[f"l{i}_attn"]["wk"],
        wv=p[f"l{i}_attn"]["wv"], wo=p[f"l{i}_attn"]["wo"],
        mlp_norm=p[f"l{i}_mlp_norm"]["scale"],
        gate=p[f"l{i}_gate"]["kernel"], up=p[f"l{i}_up"]["kernel"],
        down=p[f"l{i}_down"]["kernel"])
        for i in range(cfg["num_hidden_layers"])]
    return ref.Weights(embed=p["tok_emb"]["kernel"], layers=layers,
                       final_norm=p["final_norm"]["scale"],
                       head=p["lm_head"]["kernel"])


def reference_logits(cfg: Dict):
    """(Weights, ids (S,)) -> (S, V) float32 logits; the caller jits it."""
    import functools

    return functools.partial(
        ref.logits, rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def reference_loss(cfg: Dict):
    """(Weights, ids (S,), labels (S,)) -> mean cross-entropy."""
    import functools

    return functools.partial(
        ref.sequence_loss, rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def matmul_params(cfg: Dict) -> int:
    """Parameters that a token passes through in a matrix multiplication
    (the embedding is a lookup and is left out)."""
    e, hd = cfg["hidden_size"], cfg["head_dim"]
    per_layer = (e * cfg["num_attention_heads"] * hd            # wq
                 + 2 * e * cfg["num_key_value_heads"] * hd      # wk, wv
                 + cfg["num_attention_heads"] * hd * e          # wo
                 + 3 * e * cfg["intermediate_size"])            # gate up down
    return cfg["num_hidden_layers"] * per_layer + e * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained token
    (copied from bench.py `_flops_per_token`): 2 per multiply-add, three
    passes (forward, two in backward) over every matrix a token meets, plus
    causal attention at half density; recomputation is not counted."""
    dense = 6.0 * matmul_params(cfg)
    attn = 6.0 * cfg["num_hidden_layers"] * seq * (
        cfg["num_attention_heads"] * cfg["head_dim"])
    return dense + attn
