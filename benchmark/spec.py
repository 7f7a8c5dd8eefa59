"""BENCHMARK.json and the data files it names, read and checked.

A cell is found by name: its configuration's `file`, its traffic mix at
`benchmark/traffic/<traffic>.json` and each per-layer metric's definition at
`benchmark/metrics/<name>.json`. A later PR adds files and entries and edits
none, so nothing here knows a configuration, a mix or a metric by name.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class SpecError(ValueError):
    """BENCHMARK.json or a file it names breaks the benchmark's rules."""


def check_name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise SpecError(f"{what}: {value!r} is not a name (letters, digits, "
                        "'_', '.', '-', at most 64, no leading '.' or '-')")
    return value


def check_unit(value, what: str) -> str:
    if not isinstance(value, str) or not UNIT_RE.match(value):
        raise SpecError(f"{what}: {value!r} is not a unit (1-16 of letters, "
                        "digits, '_', '/', '%', '.', '-')")
    return value


def check_line(value, what: str) -> str:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value or "\r" in value):
        raise SpecError(f"{what}: needs 1-200 characters on one line")
    return value


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    if not isinstance(entry, dict):
        raise SpecError(f"{what}: not an object")
    missing = required - set(entry)
    extra = set(entry) - required - optional
    if missing or extra:
        raise SpecError(f"{what}: missing keys {sorted(missing)}, "
                        f"unknown keys {sorted(extra)}")


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple]      # None = every cell
    bound: Optional[float] = None   # end-to-end only
    layer: Optional[str] = None     # per-layer only
    moves: Optional[str] = None     # per-layer only
    reader: Optional[dict] = None   # per-layer only: {"name": ..., args}

    def in_cell(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file
    end_to_end: tuple       # Metric, those this cell reports
    per_layer: tuple        # Metric, those this cell reports


def _load_json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{what}: no file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{what}: {path} is not JSON ({e})") from None
    if not isinstance(doc, dict):
        raise SpecError(f"{what}: {path} is not a JSON object")
    return doc


def _metric(entry: dict, per_layer: bool, cells: set, idx: int) -> Metric:
    what = f"{'per_layer' if per_layer else 'end_to_end'}[{idx}]"
    required = {"name", "unit", "better", "source"}
    required |= {"layer", "moves"} if per_layer else {"bound"}
    _keys(entry, required, {"workloads"}, what)
    name = check_name(entry["name"], what + ".name")
    check_unit(entry["unit"], what + ".unit")
    if entry["better"] not in ("lower", "higher"):
        raise SpecError(f"{what}.better: 'lower' or 'higher'")
    if entry["source"] not in SOURCES:
        raise SpecError(f"{what}.source: one of {SOURCES}")
    if not per_layer and entry["source"] not in ("host_clock",
                                                 "device_trace"):
        raise SpecError(f"{what}: an end-to-end metric is taken by the "
                        "benchmark itself (host_clock or device_trace)")
    workloads = entry.get("workloads")
    if workloads is not None:
        if not isinstance(workloads, list) or not workloads:
            raise SpecError(f"{what}.workloads: a non-empty list")
        for w in workloads:
            if w not in cells:
                raise SpecError(f"{what}.workloads: no cell {w!r}")
        workloads = tuple(workloads)
    if per_layer:
        check_line(entry["layer"], what + ".layer")
        check_name(entry["moves"], what + ".moves")
        return Metric(name, entry["unit"], entry["better"], entry["source"],
                      workloads, layer=entry["layer"], moves=entry["moves"])
    bound = entry["bound"]
    if not isinstance(bound, (int, float)) or not 0.01 <= bound <= 0.1:
        raise SpecError(f"{what}.bound: a share from 0.01 to 0.1")
    return Metric(name, entry["unit"], entry["better"], entry["source"],
                  workloads, bound=float(bound))


def load(root: str) -> Dict:
    """Read `<root>/BENCHMARK.json`, check it against the contract's
    rules of form, and return {"doc", "cells": {name: Cell}, "root"}.
    The data files of every cell are read and checked too, so a bad file
    is refused before anything runs."""
    path = os.path.join(root, "BENCHMARK.json")
    doc = _load_json(path, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        raise SpecError("BENCHMARK.json is over 64 KiB")
    if set(doc) != TOP_KEYS:
        raise SpecError(f"BENCHMARK.json: keys must be exactly "
                        f"{sorted(TOP_KEYS)}, found {sorted(doc)}")
    command, paths = doc["command"], doc["paths"]
    if (not isinstance(command, list) or not 1 <= len(command) <= 32
            or not all(isinstance(c, str) for c in command)):
        raise SpecError("command: a list of 1-32 strings")
    for word in command:
        check_line(word, "command")
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        raise SpecError("paths: 1 to 16 directories")
    for p in paths:
        if (not isinstance(p, str) or not PATH_RE.match(p)
                or p.startswith("/") or ".." in p.split("/")):
            raise SpecError(f"paths: {p!r} is not a relative path inside "
                            "the repo")
    rs = doc["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        raise SpecError("run_seconds: a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    configs: Dict[str, dict] = {}
    if not isinstance(doc["configs"], list) or not 1 <= len(
            doc["configs"]) <= 24:
        raise SpecError("configs: 1 to 24 entries")
    files = set()
    for i, c in enumerate(doc["configs"]):
        what = f"configs[{i}]"
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(), what)
        name = check_name(c["name"], what + ".name")
        if name in configs:
            raise SpecError(f"{what}: two configurations named {name!r}")
        check_line(c["source"], what + ".source")
        check_line(c["why"], what + ".why")
        f = c["file"]
        if not isinstance(f, str) or not PATH_RE.match(f) \
                or not under_paths(f) or f in files:
            raise SpecError(f"{what}.file: a file of its own under `paths`")
        files.add(f)
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            raise SpecError(f"{what}.reduced: a list of at most 16 keys")
        for k in c["reduced"]:
            check_name(k, what + ".reduced")
        configs[name] = c

    if not isinstance(doc["workloads"], list) or not 1 <= len(
            doc["workloads"]) <= 24:
        raise SpecError("workloads: 1 to 24 cells")
    cell_entries: Dict[str, dict] = {}
    pairs = set()
    for i, w in enumerate(doc["workloads"]):
        what = f"workloads[{i}]"
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(), what)
        name = check_name(w["name"], what + ".name")
        check_name(w["config"], what + ".config")
        check_name(w["traffic"], what + ".traffic")
        check_line(w["why"], what + ".why")
        if name in cell_entries:
            raise SpecError(f"{what}: two cells named {name!r}")
        if w["config"] not in configs:
            raise SpecError(f"{what}: no configuration {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"{what}: configuration and traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            raise SpecError(f"{what}.chips: 1 or 4")
        cell_entries[name] = w
    unused = set(configs) - {w["config"] for w in cell_entries.values()}
    if unused:
        raise SpecError(f"configurations used by no cell: {sorted(unused)}")
    four = sum(1 for w in cell_entries.values() if w["chips"] == 4)
    if four > max(1, len(cell_entries) // 4):
        raise SpecError(f"{four} four-chip cells among {len(cell_entries)}")

    cells = set(cell_entries)
    for key, lo, hi in (("end_to_end", 1, 16), ("per_layer", 1, 128)):
        if not isinstance(doc[key], list) or not lo <= len(doc[key]) <= hi:
            raise SpecError(f"{key}: {lo} to {hi} metrics")
    e2e = [_metric(m, False, cells, i)
           for i, m in enumerate(doc["end_to_end"])]
    layer = [_metric(m, True, cells, i)
             for i, m in enumerate(doc["per_layer"])]
    names = [m.name for m in e2e + layer]
    if len(set(names)) != len(names):
        raise SpecError("two metrics share a name")
    by_name = {m.name: m for m in e2e}
    if "setup_s" not in by_name or by_name["setup_s"].workloads is not None:
        raise SpecError("end_to_end: `setup_s` must be there, in every cell")
    metrics_dir = os.path.join(root, "benchmark", "metrics")
    resolved: List[Metric] = []
    for m in layer:
        if m.moves not in by_name:
            raise SpecError(f"{m.name}: moves {m.moves!r}, which is no "
                            "end-to-end metric")
        for cell in cells:
            if m.in_cell(cell) and not by_name[m.moves].in_cell(cell):
                raise SpecError(f"{m.name}: cell {cell} does not report "
                                f"{m.moves}")
        f = _load_json(os.path.join(metrics_dir, m.name + ".json"),
                       f"metric {m.name}")
        # which cells report it is BENCHMARK.json's alone to say, so that a
        # new cell can join a metric without an edit to the metric's file
        _keys(f, {"name", "unit", "layer", "moves", "source", "reader"},
              {"what"}, f"metrics/{m.name}.json")
        same = (f["name"] == m.name and f["unit"] == m.unit
                and f["layer"] == m.layer and f["moves"] == m.moves
                and f["source"] == m.source)
        if not same:
            raise SpecError(f"metrics/{m.name}.json disagrees with "
                            "BENCHMARK.json")
        reader = f["reader"]
        if not isinstance(reader, dict) or "name" not in reader:
            raise SpecError(f"metrics/{m.name}.json: reader needs a name")
        check_name(reader["name"], f"metrics/{m.name}.json reader")
        resolved.append(dataclasses.replace(m, reader=reader))

    out: Dict[str, Cell] = {}
    for name, w in cell_entries.items():
        cfg = _load_json(os.path.join(root, configs[w["config"]]["file"]),
                         f"configuration {w['config']}")
        traffic = _load_json(
            os.path.join(root, "benchmark", "traffic",
                         w["traffic"] + ".json"), f"traffic {w['traffic']}")
        for doc_, key, where in ((cfg, "family", "configuration"),
                                 (traffic, "kind", "traffic")):
            if key not in doc_:
                raise SpecError(f"{where} of cell {name}: no {key!r}")
            check_name(doc_[key], f"{where} {key}")
        mine_e = tuple(m for m in e2e if m.in_cell(name))
        mine_l = tuple(m for m in resolved if m.in_cell(name))
        if len(mine_e) < 2 or not mine_l:
            raise SpecError(f"cell {name}: needs setup_s, one more "
                            "end-to-end metric and a per-layer metric")
        out[name] = Cell(name, w["config"], w["traffic"], w["chips"], cfg,
                         traffic, mine_e, mine_l)
    return {"doc": doc, "cells": out, "root": root}
