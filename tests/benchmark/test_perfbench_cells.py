"""Each traffic kind end to end on the CPU at a tiny size, kernels
interpreted: the cells are throw-away configurations, mixes and metrics
added as files only (perfbench_helpers.make_root). What runs here is the
control flow and the counting; no number from it is a device metric."""

import time

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec


@pytest.fixture()
def cells(tmp_path, monkeypatch):
    monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    root = h.make_root(tmp_path)
    return root, spec.load(root)["cells"]


def _run(cells, name, trace, seconds=3.0, seed=2 ** 31 + 11):
    root, by_name = cells
    return harness.run_cell(
        by_name[name], seed=seed, seconds=seconds, trace=trace, root=root,
        t_process_start=time.monotonic(), device=device.attached())


def _shape_ok(result, cell, trace):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    want = cell.per_layer if trace else cell.end_to_end
    units = {m.name: m.unit for m in want}
    for name, v in result["metrics"].items():
        assert v["unit"] == units[name]
        assert isinstance(v["value"], float)
    if not trace:
        assert set(result["metrics"]) == set(units)
    else:
        assert {"busy_s", "window_s"} <= set(result["device"])


def test_open_loop_cell_tiny(cells):
    cell = cells[1]["tiny-serve.tiny-open"]
    res = _run(cells, cell.name, trace=False)
    _shape_ok(res, cell, False)
    assert res["correct"] and res["failed"] == 0
    # every request due inside the window was judged
    assert 8 <= res["attempted"] <= 16
    m = res["metrics"]
    assert m["ttft_p90"]["value"] > 0 and m["tpot_p90"]["value"] > 0
    assert m["setup_s"]["value"] > 1.0      # build, warm-up and the ramp


def test_closed_loop_cell_tiny_traced(cells):
    cell = cells[1]["tiny-serve.tiny-closed"]
    res = _run(cells, cell.name, trace=True)
    _shape_ok(res, cell, True)
    assert res["correct"] and res["attempted"] > 3
    m = res["metrics"]
    # counters and spans are read on the CPU too; the device trace holds no
    # TPU plane here, so the device metric is left out, not made up
    assert m["launch_shapes"]["value"] == 17
    assert 0 < m["padded_row_share"]["value"] < 100
    assert m["preemptions"]["value"] >= 0
    assert m["step_ms.prefill"]["value"] > 0
    assert m["compile_s"]["value"] > 0
    assert "attn_share.prefill" not in m
    assert res["device"]["busy_s"] == 0.0


def test_closed_loop_cell_tiny_counts_tokens_not_whole_requests(cells):
    cell = cells[1]["tiny-serve.tiny-closed"]
    res = _run(cells, cell.name, trace=False)
    _shape_ok(res, cell, False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 3
    # tokens served inside the window, by the share of each request's
    # spans inside it: not a whole multiple of 1/seconds as a count of
    # completed requests' tokens would be
    served = res["metrics"]["serve_tok_s"]["value"] * 3.0
    assert served > 30 and abs(served - round(served)) > 1e-6


def test_wrong_tokens_are_not_correct(cells, monkeypatch):
    """The comparison that decides `correct` can fail: a server that hands
    back other tokens than it computed is caught by the reference."""
    from benchmark import serving

    real = serving.Served._done

    def corrupt(self, req, fut):
        real(self, req, fut)
        if req.tokens is not None:
            req.tokens = (req.tokens + 1) % 512

    monkeypatch.setattr(serving.Served, "_done", corrupt)
    res = _run(cells, "tiny-serve.tiny-open", trace=False, seconds=2.0)
    assert res["correct"] is False and res["attempted"] > 0


def test_training_cell_tiny_on_a_virtual_mesh(cells):
    cell = cells[1]["tiny-train.tiny-steps"]
    res = _run(cells, cell.name, trace=False, seconds=2.0)
    _shape_ok(res, cell, False)
    assert res["correct"], res
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["metrics"]["train_tok_s"]["value"] > 0
