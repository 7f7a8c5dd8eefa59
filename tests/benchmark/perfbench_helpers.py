"""A throw-away benchmark root for the CPU tests: the repo's own data files
plus tiny configurations, mixes and metrics ADDED AS FILES ONLY, which is how
a later PR adds a cell."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# head_dim 128 so the ragged and flash kernels' gates pass (interpreted)
TINY = {
    "family": "mistral", "hidden_size": 256, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 128, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
    "sliding_window": None, "tie_word_embeddings": False,
}
TINY_SERVE = dict(TINY, server={
    "paged": True, "slots": 2, "max_len": 64, "page_size": 16,
    "num_pages": 9, "prefill_chunk": 8, "kv_dtype": "auto"},
    check={"sample": 2, "tie_tol_sigma": 0.05,
           "kernel_variant": "ragged_pallas", "kv_cache_dtype": "bfloat16"})
TINY_TRAIN = dict(TINY, trainer={
    "mesh": {"data": 2, "model": 2}, "strategy": "llama_tp_strategy",
    "remat": "hidden", "batch": 4, "seq": 128, "lr": 1e-3,
    "adam_state_dtype": "bfloat16"},
    check={"attention_kernel": "pallas_flash", "loss_rtol": 2e-2})
TINY_OPEN = {
    "kind": "open_poisson", "rate_rps": 4.0, "ramp_s": 0.5, "drain_s": 20.0,
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 3, "max": 30},
    "new_tokens": {"dist": "uniform", "min": 3, "max": 5}, "sizes_seed": 1}
TINY_CLOSED = {
    "kind": "closed_clients", "clients": 3, "ramp_s": 0.5, "requests": 2000,
    "prompt_tokens": {"dist": "uniform", "min": 10, "max": 40},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 4}, "sizes_seed": 2}
TINY_STEPS = {"kind": "train_steps", "warm_steps": 2, "in_flight": 2,
              "check_sequences": 2}


def make_root(tmp_path, extra_metric=None):
    """Copy the repo's BENCHMARK.json and data directories, then add a tiny
    configuration, mix and cell of each traffic kind by writing new files
    and new entries; nothing that was there is edited."""
    root = str(tmp_path)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)

    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("benchmark/configs/tiny-serve.json", TINY_SERVE)
    write("benchmark/configs/tiny-train.json", TINY_TRAIN)
    write("benchmark/traffic/tiny-open.json", TINY_OPEN)
    write("benchmark/traffic/tiny-closed.json", TINY_CLOSED)
    write("benchmark/traffic/tiny-steps.json", TINY_STEPS)
    for name in ("tiny-serve", "tiny-train"):
        doc["configs"].append({
            "name": name, "source": "none: a test's throw-away sizes",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "CPU rehearsal"})
    cells = {"tiny-serve.tiny-open": ("tiny-serve", "tiny-open", 1),
             "tiny-serve.tiny-closed": ("tiny-serve", "tiny-closed", 1),
             "tiny-train.tiny-steps": ("tiny-train", "tiny-steps", 1)}
    for name, (cfg, traffic, chips) in cells.items():
        doc["workloads"].append({"name": name, "config": cfg,
                                 "traffic": traffic, "chips": chips,
                                 "why": "CPU rehearsal"})
    like = {"tiny-serve.tiny-open": "mistral-7b-serve1.chat-steady",
            "tiny-serve.tiny-closed": "mistral-7b-serve1.longdoc-backlog",
            "tiny-train.tiny-steps": "mistral-7b-train4.pretrain-seq4096"}
    # the tiny cells join the metrics of the cells they are shaped like:
    # entries of BENCHMARK.json grow, no metric's file is touched
    for key in ("end_to_end", "per_layer"):
        for m in doc[key]:
            if "workloads" in m:
                m["workloads"] = m["workloads"] + [
                    t for t, real in like.items() if real in m["workloads"]]
    if extra_metric is not None:
        entry, file_ = extra_metric
        doc["per_layer"].append(entry)
        write(f"benchmark/metrics/{entry['name']}.json", file_)
    write("BENCHMARK.json", doc)
    return root
