"""BENCHMARK.json and its data files: the repo's own load, a malformed one is
refused before anything runs, and a configuration, a traffic mix and a
per-layer metric can each be added as new files with no edit to one that is
there."""

import json
import os

import pytest

import perfbench_helpers as h
from benchmark import spec


def _doc():
    with open(os.path.join(h.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_changed(tmp_path, change):
    root = h.make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)
    return spec.load(root)


def test_the_repos_benchmark_loads():
    loaded = spec.load(h.REPO)
    cells = loaded["cells"]
    assert set(cells) == {w["name"] for w in _doc()["workloads"]}
    for cell in cells.values():
        names = [m.name for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert cell.name == f"{cell.config_name}.{cell.traffic_name}"


def test_at_most_one_four_chip_cell_and_widths_are_published():
    doc = _doc()
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= 1
    for c in doc["configs"]:
        with open(os.path.join(h.REPO, c["file"])) as f:
            cfg = json.load(f)
        # Mistral-7B-v0.3's config.json; only the depth is reduced
        assert c["reduced"] == ["num_hidden_layers"]
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
        assert cfg["rope_theta"] == 1e6 and cfg["rms_norm_eps"] == 1e-5


def test_every_per_layer_metrics_cells_report_what_it_moves():
    doc = _doc()
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = [w["name"] for w in doc["workloads"]]
    for m in doc["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)


BAD_NAMES = ["has space", "a,b", "a/b", "", "x" * 65, "-lead", ".lead",
             "grεεk"]


@pytest.mark.parametrize("bad", BAD_NAMES)
def test_a_name_outside_the_allowed_characters_is_refused(tmp_path, bad):
    def change(doc):
        doc["per_layer"][0]["name"] = bad

    with pytest.raises(spec.SpecError, match="is not a name"):
        _load_changed(tmp_path, change)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17,
                                 "a,b"])
def test_a_unit_outside_the_allowed_characters_is_refused(tmp_path, bad):
    def change(doc):
        doc["end_to_end"][0]["unit"] = bad

    with pytest.raises(spec.SpecError, match="is not a unit"):
        _load_changed(tmp_path, change)


@pytest.mark.parametrize("change,match", [
    (lambda d: d.update(extra=1), "keys must be exactly"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["end_to_end"][0].update(why="x"), "unknown keys"),
    (lambda d: d["end_to_end"][0].update(source="program_span"),
     "taken by the benchmark itself"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "no end-to-end"),
    (lambda d: d["paths"].append("../out"), "relative path"),
    (lambda d: d["configs"][0].update(file="bench.py"), "under `paths`"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0], name="again")),
     "appear twice"),
    (lambda d: d["end_to_end"].pop(
        [m["name"] for m in d["end_to_end"]].index("setup_s")), "setup_s"),
])
def test_a_malformed_benchmark_is_refused(tmp_path, change, match):
    with pytest.raises(spec.SpecError, match=match):
        _load_changed(tmp_path, change)


def test_a_metric_whose_cell_lacks_what_it_moves_is_refused(tmp_path):
    def change(doc):
        m = next(m for m in doc["per_layer"] if m["name"] == "step_ms.decode")
        m["workloads"] = ["mistral-7b-serve1.longdoc-backlog"]

    with pytest.raises(spec.SpecError, match="does not report"):
        _load_changed(tmp_path, change)


def test_configuration_mix_and_metric_are_added_as_files_only(tmp_path):
    """make_root copies the repo's data files untouched and ADDS a tiny
    configuration, mixes and one new per-layer metric with a reader's
    arguments; the loader resolves all of it with no file edited."""
    entry = {"name": "tiny.hit_share", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "page pool",
             "moves": "serve_tok_s",
             "workloads": ["tiny-serve.tiny-closed"]}
    file_ = {k: entry[k] for k in ("name", "unit", "layer", "moves",
                                   "source")}
    file_["reader"] = {"name": "counter", "key": "prefix_hit_tokens",
                       "over": "launch_rows", "scale": 100}
    before = {}
    for sub in ("configs", "traffic", "metrics"):
        d = os.path.join(h.REPO, "benchmark", sub)
        for name in os.listdir(d):
            with open(os.path.join(d, name), "rb") as f:
                before[(sub, name)] = f.read()
    root = h.make_root(tmp_path, extra_metric=(entry, file_))
    cells = spec.load(root)["cells"]
    for (sub, name), data in before.items():
        with open(os.path.join(root, "benchmark", sub, name), "rb") as f:
            assert f.read() == data
    closed = cells["tiny-serve.tiny-closed"]
    assert closed.config["hidden_size"] == 256
    assert closed.traffic["kind"] == "closed_clients"
    mine = {m.name: m for m in closed.per_layer}
    assert mine["tiny.hit_share"].reader["key"] == "prefix_hit_tokens"
    assert "padded_row_share" in mine
    assert "tiny.hit_share" not in {
        m.name for m in cells["tiny-serve.tiny-open"].per_layer}


def test_metric_files_match_benchmark_json():
    doc = _doc()
    d = os.path.join(h.REPO, "benchmark", "metrics")
    assert {n[:-5] for n in os.listdir(d)} == {
        m["name"] for m in doc["per_layer"]}
