"""The Ling-3.0-flash configuration, its cell, its traffic file, its shape
functions and its four metrics: the files load through benchmark/spec.py
with every published width unchanged, the shape functions count hand-made
launches, and the cell runs end to end on the CPU at a tiny size, traced,
through pages and per-slot states (no number from it is a device metric)."""

import json
import os
import time

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec
from benchmark.families import ling3 as fam
from benchmark.shape_fns import (kda_ragged_launch, mla_hybrid_launch,
                                 mla_paged_launch, moe_grouped_launch)

CELL = "ling-3-flash-serve1.hybrid-longctx-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"kda_share", "kda_roofline", "state_bytes_per_slot",
               "mla_hybrid_roofline"}
JOINED = {"compile_s", "launch_shapes", "padded_row_share", "preemptions",
          "step_ms.prefill", "host_ms.prefill", "idle_launch.prefill",
          "idle_fetch.prefill", "idle_commit.prefill", "pool_in_place_share",
          "weight_bytes_per_launch.prefill", "one_launch_share",
          "launch_ahead_share.prefill", "mla_share", "moe_share",
          "moe_roofline", "experts_hit_share", "latent_bytes_per_token"}

TINY = {
    "family": "ling3", "hidden_size": 64, "num_hidden_layers": 3,
    "first_layer": 0, "first_k_dense_replace": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "head_dim": 16, "layer_group_size": 3,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "no_kda_lora": True, "linear_silu": True,
    "use_qk_norm": True, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "rotary_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000, "num_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "n_group": 2,
    "topk_group": 1, "score_function": "sigmoid",
    "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5,
    "gated_attention_proj_granularity_type": "head_wise",
    "vocab_size": 256, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "experts_held": [0, 4],
    "published": {"num_experts": 8, "first_k_dense_replace": 1},
    "server": {"paged": True, "slots": 2, "max_len": 64, "page_size": 16,
               "num_pages": 9, "prefill_chunk": 8, "prefix_cache": False,
               "kv_dtype": "auto"},
    # what is rehearsed here is the plumbing of the comparison, not its
    # tolerance (tests/benchmark/test_perfbench_mistral4.py says why 3.0)
    "check": {"sample": 2, "tie_tol_sigma": 3.0,
              "kernel_variant": "ragged_gather",
              "kv_cache_dtype": "bfloat16"},
}


def test_the_ling3_files_load_and_keep_the_published_widths():
    cells = spec.load(h.REPO)["cells"]
    cell = cells[CELL]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_clients"
    t = cell.traffic
    assert (t["clients"], t["ramp_s"]) == (16, 10.0)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.7, "min": 1024, "max": 32768}
    assert t["new_tokens"] == {"dist": "uniform", "min": 32, "max": 192}
    assert [m.name for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    names = {m.name for m in cell.per_layer}
    # INCLUDES what ISSUE 44 names: a later PR may join it to more
    assert NEW_METRICS | JOINED <= names
    assert "mla_roofline" not in names      # it would count 7 layers for 1
    for m in cell.per_layer:
        if m.name in NEW_METRICS:
            assert m.moves == "serve_tok_s" and CELL in m.workloads
    cfg = cell.config
    fam.check(cfg)
    reduced = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
               "num_experts": 128, "vocab_size": 19648}
    assert set(cfg["reduced"]) == set(reduced) == set(cfg["published"])
    for key in ("published", "assumed", "deployment", "bytes",
                "server_notes"):
        assert cfg[key]
    # the limit has to fail the float8 control at the check's OWN sample
    # size: 0.43 % of float8's tokens lie beyond 2.75 sigma (check.why),
    # so about 112 served tokens a request x 12 requests see 5 or 6
    chk = cfg["check"]
    assert chk["why"] and chk["sample"] >= 12 and chk["tie_tol_sigma"] <= 2.75
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    srv = cfg["server"]
    assert srv["max_len"] >= 32768 + 192 and srv["prefix_cache"] is False
    assert srv["slots"] == 8 and srv["prefill_chunk"] >= 256
    pages = -(-srv["max_len"] // srv["page_size"])
    assert srv["num_pages"] == srv["slots"] * pages + 1
    # one dense layer, then a whole period: five KDA to one MLA
    kinds = fam.layer_kinds(cfg)
    assert kinds == ("kda", "kda", "kda", "kda", "mla", "kda", "kda")
    assert kinds[1:].count("kda") == 5 and fam.dense_layers(cfg) == 1
    p = fam.program_config(cfg)
    assert (p.n_experts, p.experts_held, p.experts_per_tok, p.n_group,
            p.topk_group) == (512, (0, 128), 8, 8, 4)
    assert (p.dim, p.heads, p.kda_head_dim, p.conv_taps, p.kv_lora_rank,
            p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim,
            p.expert_hidden, p.shared_hidden, p.dense_hidden) == (
        2560, 32, 128, 4, 512, 128, 64, 128, 768, 768, 6144)
    # a clamp in a kept layer is refused, not ignored
    clamped = dict(cfg, expert_swiglu_limit_list=[0, 0, 4] + [0] * 39)
    with pytest.raises(ValueError, match="clamps a kept layer"):
        fam.check(clamped)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value


def test_the_bytes_paragraph_counts_the_programs_own_leaves():
    """The configuration's parameter count, from the shapes the program's
    attrs declare at the published widths (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.ling3 import build_ling3

    cfg = spec.load(h.REPO)["cells"][CELL].config
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_ling3(ff, fam.program_config(cfg), batch_size=1, seq_len=8)
    per_node = {}
    for n in ff.graph.topo_order():
        ins = ff.graph.input_shapes(n)
        size = 0
        for w in n.attrs.weights(*ins).values():
            k = 1
            for d in w.shape.dims:
                k *= d
            size += k
        per_node[n.name] = size
    total = sum(per_node.values())
    assert per_node["l0_attn"] == 63_049_888          # a KDA layer
    assert per_node["l4_attn"] == 31_965_696          # the MLA layer
    assert per_node["l1_moe"] == 762_184_192          # 128 held + shared
    assert total == 5_131_192_256
    assert f"{total:,}" in cfg["bytes"]
    # a slot's state: six layers of 32 x 128 x 128 float32 + 3 conv rows
    state = 6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert state == 13_025_280 and f"{state:,}" in cfg["bytes"]


def test_shape_functions_on_hand_counted_launches():
    cfg = spec.load(h.REPO)["cells"][CELL].config
    # a 256-row chunk (32 pieces) at position 8192 beside 7 decode rows:
    # 8 slots' states touched, 263 live rows
    attrs = {"state_slots": 8, "kda_rows": 263, "kda_pieces": 39,
             "latent_pages": 132 + 7 * 10,
             "qk_pairs": 256 * 8192 + 256 * 257 // 2 + 7 * 600,
             "experts_hit": [128] * 6, "moe_assignments": [526] * 6}
    need = kda_ragged_launch.per_launch(attrs, cfg, 2)
    state = 32 * 128 * 128 * 4
    row = 32 * (5 * 128 + 1) * 4
    assert need == [(2.0 * 8 * state + 263 * row,
                     263.0 * 32 * 7 * 128 * 128)] * 6
    assert state == 2_097_152
    # the latent layer is counted once, where the accepted function would
    # count it seven times
    one = mla_hybrid_launch.per_launch(attrs, cfg, 2)
    seven = mla_paged_launch.per_launch(attrs, cfg, 2)
    assert len(one) == 1 and len(seven) == 7 and one[0] == seven[0]
    assert one[0] == (202 * 64 * 576 * 2.0,
                      attrs["qk_pairs"] * 32 * 2.0 * (576 + 512))
    # the accepted expert function reads this configuration's widths and
    # zips the six EXPERT layers' counters
    expert = 3 * 2560 * 768 * 2.0
    assert moe_grouped_launch.per_launch(attrs, cfg, 2) == [
        (128 * expert, 6.0 * 526 * 2560 * 768)] * 6
    assert abs(expert * 128 * 6 - 9.06e9) < 1e7      # the layers' 9.06 GB
    # a parent's span has none of the counters: nothing to read, no error
    assert kda_ragged_launch.per_launch({"kv_pages": 3}, cfg, 2) is None
    assert mla_hybrid_launch.per_launch({"kv_pages": 3}, cfg, 2) is None


def _tiny_root(tmp_path):
    """A copy of the benchmark with TINY as the configuration `tiny-l3`
    and the cell `tiny-l3.tiny-closed` beside the real ones."""
    root = h.make_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "benchmark/configs/tiny-l3.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny-l3", "source": "none",
                           "file": "benchmark/configs/tiny-l3.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny-l3.tiny-closed",
                             "config": "tiny-l3", "traffic": "tiny-closed",
                             "chips": 1, "why": "CPU rehearsal"})
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("tiny-l3.tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def test_the_ling3_cell_runs_end_to_end_tiny_and_traced(tmp_path):
    # the gather fallback, the dense expert loop and the scan over items:
    # what is rehearsed here is the harness, the family, pages and states
    # under a real closed loop and the readers (tests/test_ling3.py runs
    # the kernels, interpreted, against the reference)
    root = _tiny_root(tmp_path)
    cell = spec.load(root)["cells"]["tiny-l3.tiny-closed"]
    res = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=3.0, trace=True,
                           root=root, t_process_start=time.monotonic(),
                           device=device.attached())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 2
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # two KDA layers: (4 x 16 x 16 float32 + 3 rows x 192 bfloat16) each
    assert m["state_bytes_per_slot"] == 2 * (4096 + 3 * 192 * 2)
    assert m["latent_bytes_per_token"] == 128 * 2       # one latent layer
    assert 0 < m["experts_hit_share"] <= 100
    # the pool and the four state leaves are written where they lie
    assert m["preemptions"] == 0 and m["pool_in_place_share"] == 100
    # a state graph's launches: (2, 1) and (2, 8), and two sampling programs
    assert m["launch_shapes"] == 4 and m["step_ms.prefill"] > 0
    # no TPU plane on the CPU: the device metrics are left out, not made up
    assert not {"kda_share", "kda_roofline", "mla_hybrid_roofline",
                "mla_share", "moe_share", "moe_roofline"} & set(m)


def test_the_precision_script_puts_its_control_through_the_harness_check(
        tmp_path, monkeypatch, capsys):
    """`ling3_precision.py` rehearsed at the tiny size: the tails of both
    precisions, and the stand-in's requests judged by `Served.check` under
    the configuration's own `check` block. At a limit of 0 sigma every
    token that is not the reference's argmax counts, so the float8
    stand-in must come out as not correct; at a limit no token can pass,
    as correct (what is rehearsed is the plumbing: the limits of the real
    configuration come from the chip)."""
    from benchmark.reference import ling3_precision as prec

    root = _tiny_root(tmp_path)
    monkeypatch.setattr(prec, "ROOT", root)
    path = os.path.join(root, "benchmark/configs/tiny-l3.json")
    results = {}
    for tol in (0.0, 1e9):
        cfg = dict(TINY, check=dict(TINY["check"], tie_tol_sigma=tol))
        with open(path, "w") as f:
            json.dump(cfg, f)
        assert prec.main(["--seed", str(2 ** 31 + 11), "--tokens", "48",
                          "--sequences", "2", "--control-sequences", "1",
                          "--stand-in", "2", "--stand-in-prompt", "12",
                          "--stand-in-new", "20", "--config", "tiny-l3"]) == 0
        results[tol] = json.loads(capsys.readouterr().out.splitlines()[-1])
    out = results[0.0]
    b, f8 = (out["precisions"][k] for k in ("bfloat16", "float8_e4m3fn"))
    assert (b["tokens"], f8["tokens"]) == (96, 48)
    assert b["tokens_beyond"]["0.5"] <= f8["tokens_beyond"]["0.5"]
    assert f8["argmax_share"] < 1.0
    assert set(b["chance_none_beyond"]["3.0"]) == {
        str(n) for n in prec.CHECK_SIZES}
    assert out["stand_in"]["check"] == {"sample": 2, "tie_tol_sigma": 0.0}
    assert not out["stand_in"]["correct"] and out["stand_in"]["why_not"]
    assert results[1e9]["stand_in"]["correct"]
