"""The Mellum-2 configuration, its cell, its traffic file and its three
metrics: the files load through benchmark/spec.py with every published
width unchanged, the window shape function counts hand-made launches, and
the cell runs end to end on the CPU at a tiny size, traced, through the
two classes of pages (no number from it is a device metric)."""

import json
import os
import time

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec
from benchmark.families import mellum2 as fam
from benchmark.shape_fns import moe_grouped_launch, ragged_window_launch

CELL = "mellum2-12b-serve1.repo-mixed-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"window_attn_roofline", "window_walk_share",
               "kv_resident_share"}

TINY = {
    "family": "mellum2", "hidden_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 8, "sliding_window": 16,
    "use_sliding_window": True, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 128, "norm_topk_prob": True,
    "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "experts_held": [2, 6], "published": {"num_experts": 8},
    "server": {"paged": True, "slots": 2, "max_len": 64, "page_size": 16,
               "num_pages": 9, "prefill_chunk": 8, "prefix_cache": False,
               "kv_dtype": "auto"},
    # what is rehearsed here is the plumbing of the comparison, not its
    # tolerance (tests/benchmark/test_perfbench_mistral4.py says why 3.0)
    "check": {"sample": 2, "tie_tol_sigma": 3.0,
              "kernel_variant": "ragged_gather",
              "kv_cache_dtype": "bfloat16"},
}


def test_the_mellum2_files_load_and_keep_the_published_widths():
    cells = spec.load(h.REPO)["cells"]
    cell = cells[CELL]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_clients"
    t = cell.traffic
    assert {k: t[k] for k in ("clients", "ramp_s", "drain_s", "requests",
                              "sizes_seed")} == {
        "clients": 16, "ramp_s": 10.0, "drain_s": 60.0, "requests": 1200,
        "sizes_seed": 20260930}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 1.0, "min": 256, "max": 32768}
    assert t["new_tokens"] == {"dist": "uniform", "min": 32, "max": 128}
    assert [m.name for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    names = {m.name for m in cell.per_layer}
    assert NEW_METRICS | {
        "launch_shapes", "padded_row_share", "preemptions",
        "step_ms.prefill", "host_ms.prefill", "idle_launch.prefill",
        "idle_fetch.prefill", "idle_commit.prefill", "attn_share.prefill",
        "moe_share", "moe_roofline", "experts_hit_share",
        "pool_in_place_share", "weight_bytes_per_launch.prefill",
        "one_launch_share", "compile_s"} == names
    assert "ragged_roofline.prefill" not in names   # its reader is per layer
    for m in cell.per_layer:
        if m.name in NEW_METRICS:
            assert m.moves == "serve_tok_s" and m.workloads == (CELL,)
    cfg = cell.config
    fam.check(cfg)
    reduced = {"num_hidden_layers": 12, "num_experts": 16,
               "vocab_size": 24576}
    assert set(cfg["reduced"]) == set(reduced) == set(cfg["published"])
    for key in ("published", "assumed", "deployment", "bytes"):
        assert cfg[key]
    assert cfg["check"]["why"] and cfg["check"]["sample"] == 4
    srv = cfg["server"]
    assert srv["max_len"] >= 32768 + 128 and srv["prefix_cache"] is False
    pages = -(-srv["max_len"] // srv["page_size"])
    assert srv["num_pages"] == srv["slots"] * pages + 1
    per_slot = -(-(cfg["sliding_window"] + srv["prefill_chunk"])
                 // srv["page_size"]) + 1
    assert srv["num_pages_window"] == srv["slots"] * per_slot + 1
    # three whole periods: 9 sliding layers, 3 full
    kinds = fam.layer_types(cfg)
    assert (kinds.count("sliding_attention"), kinds.count("full_attention")
            ) == (9, 3)
    p = fam.program_config(cfg)
    assert (p.n_experts, p.experts_held, p.experts_per_tok) == (64, (0, 16), 8)
    assert (p.dim, p.heads, p.kv_heads, p.head_dim, p.expert_hidden,
            p.sliding_window) == (2304, 32, 4, 128, 896, 1024)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value


def test_window_shape_function_on_hand_counted_launches():
    cfg = spec.load(h.REPO)["cells"][CELL].config
    # one slot's 128-row chunk at position 4096 (65 + 1 pages to row 4223,
    # the window from row 3073: page 48) and a decode row at 100
    attrs = {"kv_pages_full": 66 + 2, "kv_pages_window": 18 + 2,
             "qk_pairs_full": 128 * 4096 + 128 * 129 // 2 + 101,
             "qk_pairs_window": 128 * 1024 + 101,
             "experts_hit": [16] * 12, "moe_assignments": [258] * 12}
    need = ragged_window_launch.per_launch(attrs, cfg, 2)
    page = 64 * 2 * 4 * 128 * 2.0
    full = (68 * page, attrs["qk_pairs_full"] * 4.0 * 32 * 128)
    win = (20 * page, attrs["qk_pairs_window"] * 4.0 * 32 * 128)
    assert need == [win, win, win, full] * 3
    assert page == 131072.0
    # the accepted expert function reads this configuration's widths
    expert = 3 * 2304 * 896 * 2.0
    assert moe_grouped_launch.per_launch(attrs, cfg, 2) == [
        (16 * expert, 6.0 * 258 * 2304 * 896)] * 12
    assert abs(expert * 16 * 12 - 2.378e9) < 1e6     # the layers' 2.38 GB
    # a parent's span has none of the counters: nothing to read, no error
    assert ragged_window_launch.per_launch(
        {"kv_pages": 3, "qk_pairs": 1}, cfg, 2) is None


def test_the_mellum2_cell_runs_end_to_end_tiny_and_traced(tmp_path):
    # the gather fallback and the dense expert loop: what is rehearsed here
    # is the harness, the family, the two classes of pages under a real
    # closed loop and the readers (tests/test_mellum2.py runs the kernels,
    # interpreted, against the reference)
    root = h.make_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "benchmark/configs/tiny-m2.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny-m2", "source": "none",
                           "file": "benchmark/configs/tiny-m2.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny-m2.tiny-closed",
                             "config": "tiny-m2", "traffic": "tiny-closed",
                             "chips": 1, "why": "CPU rehearsal"})
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("tiny-m2.tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    cell = spec.load(root)["cells"]["tiny-m2.tiny-closed"]
    res = harness.run_cell(cell, seed=2 ** 31 + 5, seconds=3.0, trace=True,
                           root=root, t_process_start=time.monotonic(),
                           device=device.attached())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 2
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # prompts of 10-40 tokens against a window of 16: the walk is bounded
    # and pages were released, so both shares are under 100
    assert 0 < m["window_walk_share"] < 100
    assert 0 < m["kv_resident_share"] < 100
    assert 0 < m["experts_hit_share"] <= 100
    assert m["preemptions"] == 0 and m["pool_in_place_share"] == 100
    assert m["launch_shapes"] == 18 and m["step_ms.prefill"] > 0
    # no TPU plane on the CPU: the device metrics are left out, not made up
    assert not {"window_attn_roofline", "attn_share.prefill", "moe_share",
                "moe_roofline"} & set(m)
