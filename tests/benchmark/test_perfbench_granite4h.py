"""The Granite-4.0-H-Micro configuration, its cell, its traffic file, its
two shape functions and its six metrics: the files load through
benchmark/spec.py with EVERY published key unchanged (nothing is reduced),
the shape functions count hand-made launches, the family refuses a file
that lacks a published key, and the cell runs end to end on the CPU at a
tiny size, traced, through pages and per-slot states (no number from it
is a device metric)."""

import json
import os
import time

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec
from benchmark.families import granite4h as fam
from benchmark.shape_fns import ragged_gqa64_launch, ssd_ragged_launch

CONFIG = "granite-4.0-h-micro-serve1"
CELL = CONFIG + ".reasoning-decode-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"ssd_share", "ssd_roofline", "ssd_proj_share",
               "ragged64_roofline.decode", "slot_occupancy.decode",
               "kv_bytes_per_token"}
JOINED = {"compile_s", "launch_shapes", "padded_row_share", "preemptions",
          "pool_in_place_share", "one_launch_share", "state_bytes_per_slot",
          "step_ms.prefill", "host_ms.prefill", "attn_share.prefill",
          "launch_ahead_share.prefill", "uploads_per_launch.prefill",
          "weight_bytes_per_launch.prefill", "idle_launch.prefill",
          "idle_fetch.prefill", "idle_commit.prefill"}

TINY = {
    "family": "granite4h", "hidden_size": 128, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"],
    "shared_intermediate_size": 96, "intermediate_size": 96,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "mamba_n_heads": 16, "mamba_d_head": 16, "mamba_d_state": 128,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "position_embedding_type": "nope",
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8,
    "num_local_experts": 0, "num_experts_per_tok": 0, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
    "server": {"paged": True, "slots": 2, "max_len": 64, "page_size": 16,
               "num_pages": 9, "prefill_chunk": 8, "prefix_cache": False,
               "kv_dtype": "auto"},
    # what is rehearsed here is the plumbing of the comparison, not its
    # tolerance (tests/benchmark/test_perfbench_mistral4.py says why 3.0)
    "check": {"sample": 2, "tie_tol_sigma": 3.0,
              "kernel_variant": "ragged_gather",
              "kv_cache_dtype": "bfloat16"},
}


def test_the_granite4h_files_load_with_every_published_key_unchanged():
    loaded = spec.load(h.REPO)
    cell = loaded["cells"][CELL]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_clients"
    t = cell.traffic
    assert (t["clients"], t["ramp_s"], t["drain_s"], t["requests"]) == (
        64, 15.0, 60.0, 800)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.8, "min": 128, "max": 2048}
    assert t["new_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    # a fixed trace of sizes of its own
    seeds = [c.traffic.get("sizes_seed") for c in loaded["cells"].values()]
    assert seeds.count(t["sizes_seed"]) == 1
    assert [m.name for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    names = {m.name for m in cell.per_layer}
    assert NEW_METRICS | JOINED <= names
    # a tick's metrics that move tpot_p90 cannot list a serve_tok_s cell
    assert not {"step_ms.decode", "token_gap_p99"} & names
    for m in cell.per_layer:
        if m.name in NEW_METRICS:
            assert m.moves == "serve_tok_s" and m.workloads == (CELL,)
    entry = next(c for c in loaded["doc"]["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and loaded["doc"]["configs"][-1] is entry
    assert loaded["doc"]["workloads"][-1]["name"] == CELL
    cfg = cell.config
    fam.check(cfg)
    assert cfg["reduced"] == [] and cfg["reduced_why"]
    for key in ("assumed", "deployment", "bytes", "server_notes"):
        assert cfg[key]
    assert cfg["check"]["why"] and cfg["check"]["kernel_variant"] == (
        "ragged_pallas")
    srv = cfg["server"]
    assert srv["slots"] >= 32 and srv["prefix_cache"] is False
    assert (srv["prefill_chunk"], srv["page_size"], srv["max_len"]) == (
        512, 64, 3136)
    assert srv["max_len"] >= 2048 + 1024
    # every slot's longest request fits the pool: nothing is preempted
    assert srv["num_pages"] > srv["slots"] * -(-srv["max_len"]
                                              // srv["page_size"])
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    p = fam.program_config(cfg)
    assert (p.dim, p.hidden, p.heads, p.kv_heads, p.head_dim, p.mamba_heads,
            p.mamba_head_dim, p.mamba_state, p.mamba_conv, p.vocab_size) == (
        2048, 8192, 32, 8, 64, 64, 64, 128, 4, 100352)
    assert (p.embedding_multiplier, p.residual_multiplier,
            p.attention_multiplier, p.logits_scaling) == (12, 0.22, 1 / 64, 8)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key


def test_the_family_refuses_a_file_that_lacks_a_published_key():
    cfg = spec.load(h.REPO)["cells"][CELL].config
    for key in ("logits_scaling", "mamba_d_state", "layer_types",
                "attention_multiplier", "tie_word_embeddings"):
        with pytest.raises(ValueError, match="lacks"):
            fam.check({k: v for k, v in cfg.items() if k != key})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        fam.check(dict(cfg, num_hidden_layers=20))


def test_the_bytes_paragraph_counts_the_programs_own_leaves():
    """The configuration's parameter count, from the shapes the program's
    attrs declare at the published widths (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.granite4h import build_granite4h

    cfg = spec.load(h.REPO)["cells"][CELL].config
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_granite4h(ff, fam.program_config(cfg), batch_size=1, seq_len=8)
    per_node, state = {}, 0
    for n in ff.graph.topo_order():
        ins = ff.graph.input_shapes(n)
        size = 0
        for w in n.attrs.weights(*ins).values():
            k = 1
            for d in w.shape.dims:
                k *= d
            size += k
        per_node[n.name] = size
        if hasattr(n.attrs, "state_specs"):
            for shape, dt in n.attrs.state_specs(1).values():
                k = 4 if dt == "float32" else 2
                for d in shape:
                    k *= d
                state += k
    total = sum(per_node.values())
    assert per_node["l0_mixer"] == 25_847_232         # a Mamba-2 mixer
    assert per_node["l5_mixer"] == 10_485_760         # an attention layer
    assert per_node["tok_emb"] == 205_520_896 and per_node["lm_head"] == 0
    assert (per_node["l0_gate"] + per_node["l0_up"]
            + per_node["l0_down"]) == 50_331_648
    assert total == 3_191_396_096
    assert f"{total:,}" in cfg["bytes"]
    assert state == 76_437_504 and f"{state:,}" in cfg["bytes"]


def test_shape_functions_on_hand_counted_launches():
    cfg = spec.load(h.REPO)["cells"][CELL].config
    # a decode launch: 32 slots, a row each, contexts of 700 rows (11 pages)
    decode = {"state_slots": 32, "slots": 32, "ssd_rows": 32,
              "ssd_pieces": 32, "kv_pages": 32 * 11, "qk_pairs": 32 * 700}
    state = 64 * 64 * 128 * 4
    row = (2 * 4096 + 2 * 128 + 64) * 4
    assert state == 2_097_152
    assert ssd_ragged_launch.per_launch(decode, cfg, 2) == [
        (2.0 * 32 * state + 32 * row, 32.0 * 64 * 5 * 64 * 128)] * 36
    assert ragged_gqa64_launch.per_launch(decode, cfg, 2) == [
        (352.0 * 64 * 2 * 8 * 64 * 2, 4.0 * 22400 * 32 * 64)] * 4
    # a 512-row chunk of one slot beside 31 decode rows
    chunk = dict(decode, ssd_rows=543, ssd_pieces=95)
    (nbytes, flops), = set(ssd_ragged_launch.per_launch(chunk, cfg, 2))
    assert nbytes == 2.0 * 32 * state + 543 * row
    assert flops == 543.0 * 64 * 5 * 64 * 128
    # a parent's span, or another family's, has none of the counters
    assert ssd_ragged_launch.per_launch({"kv_pages": 3}, cfg, 2) is None
    assert ragged_gqa64_launch.per_launch(
        {"kv_pages": 3, "qk_pairs": 9, "kda_rows": 4}, cfg, 2) is None


def _tiny_root(tmp_path):
    """A copy of the benchmark with TINY as the configuration `tiny-g4`
    and the cell `tiny-g4.tiny-closed` beside the real ones."""
    root = h.make_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(root, "benchmark/configs/tiny-g4.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny-g4", "source": "none",
                           "file": "benchmark/configs/tiny-g4.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny-g4.tiny-closed",
                             "config": "tiny-g4", "traffic": "tiny-closed",
                             "chips": 1, "why": "CPU rehearsal"})
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("tiny-g4.tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def test_the_granite4h_cell_runs_end_to_end_tiny_and_traced(tmp_path):
    # the gather fallback and the scan over items: what is rehearsed here
    # is the harness, the family, pages and states under a real closed
    # loop and the readers (tests/test_granite4h.py runs the kernels,
    # interpreted, against the reference)
    root = _tiny_root(tmp_path)
    cell = spec.load(root)["cells"]["tiny-g4.tiny-closed"]
    res = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=3.0, trace=True,
                           root=root, t_process_start=time.monotonic(),
                           device=device.attached())
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 2
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # two Mamba layers: (16 x 16 x 128 float32 + 3 rows x 512 bfloat16)
    assert m["state_bytes_per_slot"] == 2 * (16 * 16 * 128 * 4 + 3 * 512 * 2)
    assert m["kv_bytes_per_token"] == 2 * 2 * 64 * 2    # one layer, K and V
    assert 0 < m["slot_occupancy.decode"] <= 100
    # the pool and the four state leaves are written where they lie
    assert m["preemptions"] == 0 and m["pool_in_place_share"] == 100
    # a state graph's launches: (2, 1) and (2, 8), and two sampling programs
    assert m["launch_shapes"] == 4 and m["step_ms.prefill"] > 0
    # no TPU plane on the CPU: the device metrics are left out, not made up
    assert not {"ssd_share", "ssd_roofline", "ssd_proj_share",
                "ragged64_roofline.decode", "attn_share.prefill"} & set(m)


def test_the_precision_script_puts_its_control_through_the_harness_check(
        tmp_path, monkeypatch, capsys):
    """`granite4h_precision.py` rehearsed at the tiny size: the tails of
    both precisions, and the stand-in's requests judged by `Served.check`
    under the configuration's own `check` block. At a limit of 0 sigma
    every token that is not the reference's argmax counts, so the float8
    stand-in must come out as not correct; at a limit no token can pass,
    as correct (what is rehearsed is the plumbing: the limits of the real
    configuration come from the chip)."""
    from benchmark.reference import granite4h_precision as prec

    root = _tiny_root(tmp_path)
    monkeypatch.setattr(prec, "ROOT", root)
    path = os.path.join(root, "benchmark/configs/tiny-g4.json")
    results = {}
    for tol in (0.0, 1e9):
        cfg = dict(TINY, check=dict(TINY["check"], tie_tol_sigma=tol))
        with open(path, "w") as f:
            json.dump(cfg, f)
        assert prec.main(["--seed", str(2 ** 31 + 11), "--tokens", "48",
                          "--sequences", "2", "--control-sequences", "1",
                          "--stand-in", "2", "--stand-in-prompt", "12",
                          "--stand-in-new", "20", "--config", "tiny-g4"]) == 0
        results[tol] = json.loads(capsys.readouterr().out.splitlines()[-1])
    out = results[0.0]
    b, f8 = (out["precisions"][k] for k in ("bfloat16", "float8_e4m3fn"))
    assert (b["tokens"], f8["tokens"]) == (96, 48)
    assert b["tokens_beyond"]["0.5"] <= f8["tokens_beyond"]["0.5"]
    assert f8["argmax_share"] < 1.0
    assert out["stand_in"]["check"] == {"sample": 2, "tie_tol_sigma": 0.0}
    assert not out["stand_in"]["correct"] and out["stand_in"]["why_not"]
    assert results[1e9]["stand_in"]["correct"]
