"""The GLM-5.3-Flash configuration, its cell, its traffic file, its shape
functions, its two readers and its seven metrics: the files load through
benchmark/spec.py with every published width unchanged, the bytes are
counted from the program's own attrs, the shape functions count hand-made
launches, the scope readers read hand-made name stacks, and the cell runs
end to end on the CPU at a tiny size, traced, through pages, pooled keys
and per-slot states (no number from it is a device metric)."""

import json
import os
import time

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec
from benchmark.families import glm5 as fam
from benchmark.readers import scope_share
from benchmark.shape_fns import (dsa_sparse_launch, hc_mix_launch,
                                 kda_glm5_launch, kda_ragged_launch,
                                 moe_grouped_launch)

CELL = "glm-5.3-flash-serve1.sparse-longctx-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"dsa_share", "dsa_roofline", "selected_share",
               "index_bytes_per_token", "hc_share", "hc_roofline",
               "kda_glm5_roofline"}
JOINED = {"compile_s", "launch_shapes", "padded_row_share", "preemptions",
          "step_ms.prefill", "host_ms.prefill", "idle_launch.prefill",
          "idle_fetch.prefill", "idle_commit.prefill", "moe_share",
          "moe_roofline", "experts_hit_share", "latent_bytes_per_token",
          "pool_in_place_share", "weight_bytes_per_launch.prefill",
          "one_launch_share", "launch_ahead_share.prefill",
          "state_bytes_per_slot", "kda_share"}

TINY = {
    "family": "glm5", "hidden_size": 64, "num_hidden_layers": 3,
    "first_layer": 0, "first_k_dense_replace": 1, "intermediate_size": 96,
    "layer_types": ["linear_attention", "deepseek_sparse_attention",
                    "linear_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "gate_lower_bound": -5,
                           "short_conv_kernel_size": 4,
                           "kda_layers": [0, 2], "full_attn_layers": [1]},
    "kda_gate_rank": 8, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_head_dim": 8,
    "qk_rope_head_dim": 0, "v_head_dim": 8, "mla_use_nope": True,
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 16,
    "index_kpool": 4, "index_kpool_compress": True,
    "index_kpool_always_select_tail": True,
    "indexer_rope_interleave": True, "index_rope_dim": 8,
    "index_rope_theta": 10000.0, "mhc": True, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "swiglu_limit": 10,
    "hidden_act": "silu", "attention_bias": False, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "experts_held": [0, 4],
    "published": {"n_routed_experts": 8, "first_k_dense_replace": 1,
                  "num_hidden_layers": 3},
    "server": {"paged": True, "slots": 2, "max_len": 64, "page_size": 16,
               "num_pages": 9, "prefill_chunk": 8, "prefix_cache": False,
               "kv_dtype": "auto"},
    # what is rehearsed here is the plumbing of the comparison, not its
    # tolerance (tests/benchmark/test_perfbench_mistral4.py says why 3.0)
    "check": {"sample": 2, "tie_tol_sigma": 3.0,
              "kernel_variant": "ragged_gather",
              "kv_cache_dtype": "bfloat16"},
}


def _cfg():
    return spec.load(h.REPO)["cells"][CELL].config


def test_the_glm5_files_load_and_keep_the_published_widths():
    cell = spec.load(h.REPO)["cells"][CELL]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_clients"
    t = cell.traffic
    assert (t["clients"], t["ramp_s"], t["drain_s"], t["requests"]) == (
        16, 10.0, 60.0, 400)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 16384,
                                  "sigma": 0.6, "min": 4096, "max": 32768}
    assert t["new_tokens"] == {"dist": "uniform", "min": 32, "max": 192}
    # a `sizes_seed` of its own
    mixes = os.path.join(h.REPO, "benchmark/traffic")
    seeds = []
    for name in os.listdir(mixes):
        with open(os.path.join(mixes, name)) as f:
            seeds.append(json.load(f).get("sizes_seed"))
    assert seeds.count(t["sizes_seed"]) == 1
    assert [m.name for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    names = {m.name for m in cell.per_layer}
    # INCLUDES what ISSUE 48 names: a later PR may join it to more
    assert NEW_METRICS | JOINED <= names
    # the accepted scan roofline reads keys this family's config lacks
    assert "kda_roofline" not in names and "mla_roofline" not in names
    for m in cell.per_layer:
        if m.name in NEW_METRICS:
            assert m.moves == "serve_tok_s" and m.workloads == (CELL,)
    cfg = cell.config
    fam.check(cfg)
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 36, "vocab_size": 19360}
    assert set(cfg["reduced"]) == set(reduced) == set(cfg["published"])
    for key in ("published", "assumed", "deployment", "bytes",
                "server_notes"):
        assert cfg[key]
    chk = cfg["check"]
    assert chk["why"] and chk["sample"] >= 8 and chk["tie_tol_sigma"] <= 3.5
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == cfg["published"][
        "n_routed_experts"]
    srv = cfg["server"]
    assert srv["max_len"] >= 32768 + 192 and srv["prefix_cache"] is False
    assert srv["slots"] == 8 and srv["prefill_chunk"] == 512
    pages = -(-srv["max_len"] // srv["page_size"])
    assert srv["num_pages"] == srv["slots"] * pages + 1
    # the last leading dense layer, then a whole period: three linear to
    # one sparse among the expert layers
    kinds = fam.layer_kinds(cfg)
    assert kinds == ("kda", "dsa", "kda", "kda", "kda")
    assert kinds[1:].count("kda") == 3 and fam.dense_layers(cfg) == 1
    p = fam.program_config(cfg)
    assert (p.n_experts, p.experts_held, p.experts_per_tok) == (
        288, (0, 36), 8)
    assert (p.dim, p.kda_heads, p.kda_head_dim, p.kda_gate_rank, p.conv_taps,
            p.heads, p.q_lora_rank, p.kv_lora_rank, p.qk_nope_head_dim,
            p.qk_rope_head_dim, p.v_head_dim, p.expert_hidden,
            p.shared_hidden, p.dense_hidden) == (
        4096, 64, 128, 128, 4, 64, 1536, 512, 256, 0, 256, 2048, 2048, 12288)
    assert (p.index_heads, p.index_dim, p.index_topk, p.index_pool,
            p.hc_streams, p.hc_sinkhorn_iters, p.swiglu_limit) == (
        32, 128, 2048, 4, 4, 20, 10.0)
    # every convention ISSUE 48 names is in `assumed`
    said = " ".join(cfg["assumed"])
    for word in ("REPEATED", "SUMMED", "hc_eps", "NO learned scale",
                 "alpha 0.01", "kda_gate_rank", "BOUNDED", "l2-normed",
                 "SiLU", "AFTER the norm and the rope",
                 "index_kpool_compress", "counts TOKENS",
                 "index_rope_theta", "two scale factors",
                 "index_share_for_mtp_iteration", "gpt-oss"):
        assert word in said, word
    # what is not built is refused by key, not ignored
    for key, value, match in (
            ("qk_rope_head_dim", 64, "no rope part"),
            ("n_group", 8, "no groups"),
            ("index_kpool_always_select_tail", False, "tail block"),
            ("mhc", False, "hc_mult streams"),
            ("scoring_func", "softmax", "sigmoid")):
        with pytest.raises(ValueError, match=match):
            fam.check(dict(cfg, **{key: value}))
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-5.3-Flash")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value


def test_the_bytes_paragraph_counts_the_programs_own_leaves():
    """The configuration's parameter count, from the shapes the program's
    attrs declare at the published widths (nothing is allocated): ISSUE
    48's 4,718 M."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.glm5 import build_glm5

    cfg = _cfg()
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_glm5(ff, fam.program_config(cfg), batch_size=1, seq_len=8)
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
    assert per_node["l0_attn"] == 137_732_288           # a KDA layer
    assert per_node["l1_attn"] == 124_389_632           # the sparse layer
    assert per_node["l1_attn_hc_pre"] == 393_243        # a mixing
    assert per_node["l1_moe"] == 932_315_424            # 36 held + shared
    assert sum(per_node[f"l0_{k}"] for k in ("gate", "up", "down")
               ) == 150_994_944
    assert total == 4_718_150_030
    for count in (total, per_node["l0_attn"], per_node["l1_attn"],
                  per_node["l1_moe"]):
        assert f"{count:,}" in cfg["bytes"]
    # a slot's state: four layers of 64 x 128 x 128 float32 + 3 conv rows
    state = 4 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert state == 17_367_040 and f"{state:,}" in cfg["bytes"]


def test_shape_functions_on_hand_counted_launches():
    cfg = _cfg()
    # a 512-row chunk (64 pieces) at position 16384 beside 7 decode rows
    # at position 9000: 8 slots, 519 live rows, 10 mixings
    rows = list(range(16384, 16896)) + [9000] * 7
    attrs = {
        "state_slots": 8, "kda_rows": 519, "kda_pieces": 71,
        "index_pages": 264 + 7 * 141,
        "index_blocks_scored": sum(t // 4 for t in rows),
        "selected_tokens": sum(511 * 4 + t % 4 + 1 for t in rows),
        "context_tokens": sum(t + 1 for t in rows),
        "selected_distinct": [9000], "hc_rows": 519 * 10,
        "experts_hit": [36] * 4, "moe_assignments": [519] * 4}
    # the sparse form: pooled keys of the slots' pages once, the distinct
    # latent rows once; every indexer head scores, every head attends
    (need,) = dsa_sparse_launch.per_launch(attrs, cfg, 2)
    assert need == (
        (attrs["index_pages"] * 16 * 128 + 9000 * 512) * 2.0,
        attrs["index_blocks_scored"] * 32 * 128 * 2.0
        + attrs["selected_tokens"] * 64 * 2 * 1024.0)
    assert attrs["selected_tokens"] == 519 * 2044 + sum(
        t % 4 + 1 for t in rows)
    # the mixing: the four streams read once and written once a block
    assert hc_mix_launch.per_launch(attrs, cfg, 2) == [
        (5190 * 2 * 4 * 4096 * 2.0, 0.0)]
    # the scan at THIS family's shape: 64 heads, four KDA layers
    state = 64 * 128 * 128 * 4
    row = 64 * (5 * 128 + 1) * 4
    assert kda_glm5_launch.per_launch(attrs, cfg, 2) == [
        (2.0 * 8 * state + 519 * row, 519.0 * 64 * 7 * 128 * 128)] * 4
    assert state == 4_194_304
    # the accepted function cannot: this config keeps the heads' size in
    # linear_attn_config (head_dim 0 at the top) and has no
    # layer_group_size, so the cell is NOT in kda_roofline's list
    assert cfg["head_dim"] == 0 and "layer_group_size" not in cfg
    with pytest.raises(KeyError):
        kda_ragged_launch.per_launch(attrs, cfg, 2)
    # the accepted expert function reads this configuration's widths
    expert = 3 * 4096 * 2048 * 2.0
    assert moe_grouped_launch.per_launch(attrs, cfg, 2) == [
        (36 * expert, 6.0 * 519 * 4096 * 2048)] * 4
    assert abs(expert * 36 * 4 - 7.25e9) < 1e7      # the layers' 7.25 GB
    # a parent's span has none of the counters: nothing to read, no error
    for fn in (dsa_sparse_launch, hc_mix_launch, kda_glm5_launch):
        assert fn.per_launch({"kv_pages": 3}, cfg, 2) is None


def test_scope_readers_on_hand_made_name_stacks():
    """An operation counts under a scope when the regex matches one WHOLE
    part of its name stack; no trace or no match reads None."""

    class Run:
        trace = True
        extras = {scope_share.MEMO: {
            "jit(step)/l1_attn_9/dsa_index/dot_general": 2.0,
            "jit(step)/l1_attn_9/dsa_select/while/body/reduce_sum": 1.0,
            "jit(step)/l1_attn_9/dsa_attend/mla_paged_attention": 4.0,
            "jit(step)/l1_attn_hc_pre_7/hc_mix/mul": 0.5,
            "jit(step)/hc_expand_2/hc_mix/tile": 0.25,
            "jit(step)/l0_attn_3/kda_ragged_scan": 8.0,
            "jit(step)/l0_attn_3/not_hc_mix_really/add": 16.0}}
        reduction = {"per_chip": {0: {"busy_s": 40.0}}, "window_s": 50.0}

    run = Run()
    dsa = "^dsa_(index|select|attend)$"
    assert scope_share.seconds(run, dsa) == 7.0
    assert scope_share.seconds(run, "^hc_mix$") == 0.75
    assert scope_share.read(run, dsa) == 100.0 * 7.0 / 40.0
    assert scope_share.read(run, "^hc_mix$", of="window") == 1.5
    assert scope_share.read(run, "^no_such_scope$") is None
    run.extras = {scope_share.MEMO: None}
    assert scope_share.read(run, dsa) is None
    # the two metric files name these scopes, which the program stamps
    from flexflow_tpu.ops import hyper_connection

    for name, scope in (("dsa_share", dsa), ("dsa_roofline", dsa),
                        ("hc_share", "^hc_mix$"),
                        ("hc_roofline", "^hc_mix$")):
        with open(os.path.join(h.REPO, "benchmark/metrics",
                               name + ".json")) as f:
            assert json.load(f)["reader"]["scope"] == scope
    assert hyper_connection.SCOPE == "hc_mix"


def _tiny_root(tmp_path):
    """A copy of the benchmark with TINY as the configuration `tiny-g5`
    and the cell `tiny-g5.tiny-closed` beside the real ones."""
    root = h.make_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "benchmark/configs/tiny-g5.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny-g5", "source": "none",
                           "file": "benchmark/configs/tiny-g5.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny-g5.tiny-closed",
                             "config": "tiny-g5", "traffic": "tiny-closed",
                             "chips": 1, "why": "CPU rehearsal"})
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("tiny-g5.tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def test_the_glm5_cell_runs_end_to_end_tiny_and_traced(tmp_path):
    # the gather fallback, the dense expert loop and the scan over items:
    # what is rehearsed here is the harness, the family, pages, pooled
    # keys and states under a real closed loop and the readers
    # (tests/test_glm5.py runs the kernels, interpreted, against the
    # reference)
    root = _tiny_root(tmp_path)
    cell = spec.load(root)["cells"]["tiny-g5.tiny-closed"]
    res = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=3.0, trace=True,
                           root=root, t_process_start=time.monotonic(),
                           device=device.attached())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 2
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # two KDA layers: (4 x 16 x 16 float32 + 3 rows x 192 bfloat16) each
    assert m["state_bytes_per_slot"] == 2 * (4096 + 3 * 192 * 2)
    # one sparse layer: 128 lanes of latent row, 16 values a 4 tokens
    assert m["latent_bytes_per_token"] == 128 * 2
    assert m["index_bytes_per_token"] == 16 * 2 // 4
    # prompts of the rehearsal run past 16 tokens: rows select
    assert 0 < m["selected_share"] < 100
    assert 0 < m["experts_hit_share"] <= 100
    # the pool's two entries and the four state leaves are written where
    # they lie
    assert m["preemptions"] == 0 and m["pool_in_place_share"] == 100
    assert m["launch_shapes"] == 4 and m["step_ms.prefill"] > 0
    # no TPU plane on the CPU: the device metrics are left out, not made up
    assert not {"dsa_share", "dsa_roofline", "hc_share", "hc_roofline",
                "kda_glm5_roofline", "kda_share", "moe_share",
                "moe_roofline"} & set(m)


def test_the_precision_script_puts_its_control_through_the_harness_check(
        tmp_path, monkeypatch, capsys):
    """`glm5_precision.py` rehearsed at the tiny size, as
    test_perfbench_ling3.py rehearses its sibling: the float8 stand-in is
    not correct at a limit of 0 sigma and correct at one no token can
    pass (the limits of the real configuration come from the chip)."""
    from benchmark.reference import glm5_precision as prec

    root = _tiny_root(tmp_path)
    monkeypatch.setattr(prec, "ROOT", root)
    path = os.path.join(root, "benchmark/configs/tiny-g5.json")
    results = {}
    for tol in (0.0, 1e9):
        cfg = dict(TINY, check=dict(TINY["check"], tie_tol_sigma=tol))
        with open(path, "w") as f:
            json.dump(cfg, f)
        assert prec.main(["--seed", str(2 ** 31 + 11), "--tokens", "48",
                          "--sequences", "2", "--control-sequences", "1",
                          "--stand-in", "2", "--stand-in-prompt", "12",
                          "--stand-in-new", "20", "--config", "tiny-g5"]) == 0
        results[tol] = json.loads(capsys.readouterr().out.splitlines()[-1])
    out = results[0.0]
    b, f8 = (out["precisions"][k] for k in ("bfloat16", "float8_e4m3fn"))
    assert (b["tokens"], f8["tokens"]) == (96, 48)
    assert b["tokens_beyond"]["0.5"] <= f8["tokens_beyond"]["0.5"]
    assert f8["argmax_share"] < 1.0
    assert set(b["chance_none_beyond"]["3.0"]) == {
        str(n) for n in prec.CHECK_SIZES}
    assert len(b["top8_differs_share_by_layer"]) == 2    # expert layers
    assert out["stand_in"]["check"] == {"sample": 2, "tie_tol_sigma": 0.0}
    assert not out["stand_in"]["correct"] and out["stand_in"]["why_not"]
    assert results[1e9]["stand_in"]["correct"]
