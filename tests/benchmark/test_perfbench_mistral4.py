"""The Mistral-Small-4 configuration, its cell, its reference and its
readers: the files load through benchmark/spec.py, the reference agrees
with a second, independent computation of one layer, the two roofline
functions count hand-made launches, and the cell runs end to end on the
CPU at a tiny size (no number from it is a device metric)."""

import json
import math
import os
import time

import numpy as np
import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec
from benchmark.families import mistral4 as fam
from benchmark.reference import mistral4 as ref
from benchmark.shape_fns import mla_paged_launch, moe_grouped_launch

CELL = "mistral-small-4-serve1.longctx-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY = {
    "family": "mistral4", "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 2, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 128, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "first_k_dense_replace": 0, "n_group": 1,
    "topk_group": 1, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "experts_held": [2, 6], "published": {"n_routed_experts": 8},
    "server": {"paged": True, "slots": 2, "max_len": 64, "page_size": 16,
               "num_pages": 9, "prefill_chunk": 8, "kv_dtype": "auto"},
    # bfloat16 weights, activations and pool at widths of 128 and 8
    # experts: a rounding that flips a token's 2nd and 3rd expert moves
    # its row by whole tenths of a sigma. What is rehearsed here is the
    # plumbing of the comparison, not its tolerance (the chip's is
    # measured, benchmark/reference/mistral4_precision.py)
    "check": {"sample": 2, "tie_tol_sigma": 3.0,
              "kernel_variant": "ragged_gather",
              "kv_cache_dtype": "bfloat16"},
}


def test_the_new_files_load_and_keep_the_published_widths():
    cells = spec.load(h.REPO)["cells"]
    cell = cells[CELL]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_clients"
    t = cell.traffic
    assert (t["clients"], t["ramp_s"], t["requests"]) == (16, 10.0, 240)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 4096,
                                  "max": 12288}
    assert t["new_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert [m.name for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    names = {m.name for m in cell.per_layer}
    assert {"mla_share", "mla_roofline", "moe_share", "moe_roofline",
            "experts_hit_share", "latent_bytes_per_token", "launch_shapes",
            "padded_row_share", "preemptions", "step_ms.prefill",
            "host_ms.prefill", "idle_launch.prefill", "idle_fetch.prefill",
            "idle_commit.prefill", "compile_s"} == names
    cfg = cell.config
    fam.check(cfg)
    reduced = {"num_hidden_layers": 4, "n_routed_experts": 32,
               "vocab_size": 32768}
    assert set(cfg["reduced"]) == set(reduced) == set(cfg["published"])
    assert cfg["server"]["max_len"] >= 12288 + 256
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mistral-Small-4-119B-2603")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value
    p = fam.program_config(cfg)
    assert abs(p.softmax_scale() - 0.1950) < 1e-4
    assert (p.n_experts, p.experts_held) == (128, (0, 32))


def _layer_numpy(x, w, a):
    """One block in float64 numpy, written from the equations and not
    from the reference's code: explicit loops over heads and experts."""
    def rms(v, s):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + a.rms_norm_eps) * s

    def silu(v):
        return v / (1 + np.exp(-v))

    S, d = x.shape[0], a.qk_rope_head_dim
    i = np.arange(d // 2)
    f = a.rope_theta ** (-2.0 * i / d)
    dim = lambda turns: d * math.log(
        a.rope_original_max / (turns * 2 * math.pi)) / (2 * math.log(
            a.rope_theta))
    lo = max(math.floor(dim(a.beta_fast)), 0)
    hi = min(math.ceil(dim(a.beta_slow)), d - 1)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    f = f * (1 - ramp) + f / a.rope_factor * ramp

    def rope(v, pos):           # v: (d,), pairs (2i, 2i+1)
        out = np.empty_like(v)
        c, s = np.cos(pos * f), np.sin(pos * f)
        out[0::2] = v[0::2] * c - v[1::2] * s
        out[1::2] = v[0::2] * s + v[1::2] * c
        return out

    h = rms(x, w["attn_norm"])
    c_q = rms(h @ w["w_dq"], w["q_norm"])
    kv = h @ w["w_dkv"]
    c_kv = rms(kv[:, :a.kv_lora_rank], w["kv_norm"])
    k_r = np.stack([rope(kv[t, a.kv_lora_rank:], t) for t in range(S)])
    n = a.qk_nope_head_dim
    m = 0.1 * a.mscale_all_dim * math.log(a.rope_factor) + 1
    scale = (n + d) ** -0.5 * m * m
    attn = np.zeros_like(x)
    for hd in range(a.heads):
        q = c_q @ w["w_uq"][:, hd]
        k_nope = c_kv @ w["w_ukv"][:, hd, :n]
        v = c_kv @ w["w_ukv"][:, hd, n:]
        o = np.zeros((S, v.shape[1]))
        for t in range(S):
            qt = q[t] * (1 + a.llama_4_scaling_beta * math.log(
                1 + t // a.rope_original_max))
            sc = np.array([qt[:n] @ k_nope[u] + rope(qt[n:], t) @ k_r[u]
                           for u in range(t + 1)]) * scale
            p = np.exp(sc - sc.max())
            o[t] = (p / p.sum()) @ v[:t + 1]
        attn += o @ w["wo"][hd]
    x = x + attn
    h = rms(x, w["moe_norm"])
    logits = h @ w["router"]
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for t in range(S):
        top = np.argsort(-p[t], kind="stable")[:a.experts_per_tok]
        for e in top:
            if a.held_lo <= e < a.held_hi:
                g = e - a.held_lo
                y[t] += (p[t, e] / p[t, top].sum() * a.routed_scaling_factor
                         * ((silu(h[t] @ w["w_gate"][g]) * (h[t] @ w["w_up"][g]))
                            @ w["w_down"][g]))
        y[t] += (silu(h[t] @ w["shared_gate"]) * (h[t] @ w["shared_up"])
                 ) @ w["shared_down"]
    return x + y


def test_reference_layer_against_an_independent_computation():
    import jax
    import jax.numpy as jnp

    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=24)
    a = fam.reference_arch(cfg)
    rng = np.random.default_rng(7)
    E, H, F, G = 32, a.heads, 24, 4
    shapes = {"attn_norm": (E,), "w_dq": (E, 32), "q_norm": (32,),
              "w_uq": (32, H, 16), "w_dkv": (E, 24), "kv_norm": (16,),
              "w_ukv": (16, H, 24), "wo": (H, 16, E), "moe_norm": (E,),
              "router": (E, 8), "w_gate": (G, E, F), "w_up": (G, E, F),
              "w_down": (G, F, E), "shared_gate": (E, F),
              "shared_up": (E, F), "shared_down": (F, E)}
    w = {k: rng.normal(0, 1.0 if k.endswith("norm") else s[-2] ** -0.5, s)
         for k, s in shapes.items()}
    x = rng.normal(0, 1, (40, E))
    want = _layer_numpy(x, w, a)
    with jax.default_matmul_precision("highest"):
        got = ref.layer(jnp.asarray(x, jnp.float32), ref.Layer(
            **{k: jnp.asarray(v, jnp.float32) for k, v in w.items()}), a)
    # float32 against float64 on values of order 1-10, 40 positions (past
    # 16 and 32, so the position scale and the YaRN blend are both live)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=0)


def test_roofline_functions_on_hand_counted_launches():
    cfg = spec.load(h.REPO)["cells"][CELL].config
    # a decode tick of 2 slots at positions 100 and 8191: pages of 64 rows
    # -> 2 + 128 pages; pairs 101 + 8192
    attrs = {"latent_pages": 130, "qk_pairs": 8293,
             "experts_hit": [3, 0, 32, 1], "moe_assignments": [4, 0, 260, 1]}
    need = mla_paged_launch.per_launch(attrs, cfg, 2)
    assert need == [(130 * 64 * 320 * 2.0, 8293 * 36864.0)] * 4
    need = moe_grouped_launch.per_launch(attrs, cfg, 2)
    expert = 3 * 4096 * 2048 * 2.0
    assert need == [(3 * expert, 6.0 * 4 * 4096 * 2048), (0.0, 0.0),
                    (32 * expert, 6.0 * 260 * 4096 * 2048),
                    (expert, 6.0 * 4096 * 2048)]
    assert abs(expert * 32 * 4 - 6.44e9) < 1e7       # the layers' 6.4 GB
    # a parent's span has neither counter: nothing to read, no error
    assert mla_paged_launch.per_launch({"kv_pages": 3, "qk_pairs": 1},
                                       cfg, 2) is None
    assert moe_grouped_launch.per_launch({"qk_pairs": 1}, cfg, 2) is None


def test_the_cell_runs_end_to_end_tiny_and_traced(tmp_path):
    # the gather fallback and the dense expert loop: what is rehearsed here
    # is the harness, the family and the readers (tests/test_mistral4.py
    # runs the kernels, interpreted, against the reference)
    root = h.make_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "benchmark/configs/tiny-m4.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny-m4", "source": "none",
                           "file": "benchmark/configs/tiny-m4.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny-m4.tiny-closed",
                             "config": "tiny-m4", "traffic": "tiny-closed",
                             "chips": 1, "why": "CPU rehearsal"})
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("tiny-m4.tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    cell = spec.load(root)["cells"]["tiny-m4.tiny-closed"]
    res = harness.run_cell(cell, seed=2 ** 31 + 5, seconds=3.0, trace=True,
                           root=root, t_process_start=time.monotonic(),
                           device=device.attached())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 2
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["latent_bytes_per_token"] == 2 * 128 * 2     # layers, lanes, bf16
    assert 0 < m["experts_hit_share"] <= 100
    assert m["launch_shapes"] == 17 and m["step_ms.prefill"] > 0
    # no TPU plane on the CPU: the device metrics are left out, not made up
    assert not {"mla_share", "mla_roofline", "moe_share",
                "moe_roofline"} & set(m)
