"""The readers of the serving tick's phase spans (ISSUE 24), each on spans
and planes written out by hand so every number can be checked in the head;
then the tiny closed-loop cell traced end to end on the CPU, where the
span-only metric prints and the device-joined ones are left out."""

import importlib
import time
import types

import pytest

import perfbench_helpers as h
from benchmark import device, harness, spec, tickspans

MS = 1_000_000          # nanoseconds
NEW = {"mistral-7b-serve1.chat-steady": [
           "host_ms.decode", "token_gap_p99", "idle_launch.decode",
           "idle_fetch.decode", "idle_commit.decode",
           "ragged_roofline.decode"],
       "mistral-7b-serve1.longdoc-backlog": [
           "host_ms.prefill", "idle_launch.prefill", "idle_fetch.prefill",
           "idle_commit.prefill", "ragged_roofline.prefill"]}
KERNEL = r"^ragged_paged_attention(\.\d+)? \[tpu_custom_call\]$"
CONFIG = {"num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 128,
          "server": {"page_size": 64}}


def _run(tmp_path, spans=(), planes=None, reduction=None):
    run = harness.Run(
        cell=types.SimpleNamespace(name="c", config=CONFIG), seed=0,
        seconds=1.0, trace=True, root=str(tmp_path), t_process_start=0.0,
        device={"kind": "TPU v5 lite"}, compile_clock=None)
    run.spans = list(spans)
    run.reduction = reduction
    run.extras["kv_cache_dtype"] = "bfloat16"
    if planes is not None:
        run.extras["planes"] = planes
    return run


def _span(name, t0_ms, dur_ms, **attrs):
    _span.n += 1
    return (name, int(t0_ms * MS), int(dur_ms * MS), 1,
            dict(attrs, id=_span.n, parent=None))


_span.n = 0


def _reader(name):
    return importlib.import_module("benchmark.readers." + name)


# -- the clock --------------------------------------------------------------

def test_beacon_offset_is_recovered_to_the_nanosecond():
    offset = -26_671_000_123_457        # profiler clock - monotonic clock
    stamps = [26_671_678_604_536 + i * 250 * MS for i in range(5)]
    late = [0, 3, 0, 1, 7]              # ns between the stamp and the open
    host = {"python3": [(f"ffclock:{s}", float(s + offset + d), 0.0)
                        for s, d in zip(stamps, late)]
            + [("decode_tick", 5.0, 7.0), ("ffclock:bad", 1.0, 0.0)]}
    got, residual, n = tickspans.beacon_offset(host)
    assert got == offset + 1 and residual == 6 and n == 5


def test_beacons_that_disagree_or_are_too_few_tie_nothing():
    base = 1_000 * MS
    host = {"t": [(f"ffclock:{base + i * MS}", float(base + i * MS + d), 0.0)
                  for i, d in enumerate((0, 10, 60_000))]}
    assert tickspans.beacon_offset(host) is None         # 59,990 ns apart
    host["t"][2] = (host["t"][2][0], host["t"][2][1] - 20_000, 0.0)
    assert tickspans.beacon_offset(host) == (10, 39_990, 3)
    assert tickspans.beacon_offset({"t": host["t"][:2]}) is None
    # one late beacon among eight moves no median and voids nothing
    host = {"t": [(f"ffclock:{base + i * MS}",
                   float(base + i * MS + (5 * MS if i == 3 else i % 2)), 0.0)
                  for i in range(8)]}
    offset, residual, n = tickspans.beacon_offset(host)
    assert offset == 0.5 and residual == 5 * MS - 0.5 and n == 8


# -- spans only --------------------------------------------------------------

def _timeline():
    """Three decode-only iterations of 20, 22 and 30 ms with 12, 13 and 14
    ms of fetch, one iteration with a prefill tick (40 ms, fetches of 1 and
    15 ms), one idle iteration, and a last decode iteration with no
    successor. Requests 1 and 2 decode throughout; 3 gets its first token
    in the prefill tick's commit."""
    ev, t = [], 100.0
    for wall, fetch in ((20, 12), (22, 13), (30, 14)):
        ev += [_span("tick_prep", t, 0.1), _span("decode_tick", t + 0.2, wall - 0.5),
               _span("fetch", t + 5, fetch),
               _span("commit", t + wall - 1.5, 1.0, rids=[1, 2], finished=0)]
        t += wall
    ev += [_span("tick_prep", t, 0.1), _span("prefill_tick", t + 0.2, 9),
           _span("fetch", t + 6, 1),
           _span("commit", t + 5, 4, rids=[3], finished=0),
           _span("decode_tick", t + 10, 29), _span("fetch", t + 20, 15),
           _span("commit", t + 38, 1.0, rids=[1, 2], finished=1)]
    t += 40
    ev += [_span("tick_prep", t, 1.2), _span("idle_wait", t + 0.1, 1.0)]
    t += 1.2
    ev += [_span("tick_prep", t, 0.1), _span("decode_tick", t + 0.2, 18),
           _span("fetch", t + 5, 11),
           _span("commit", t + 17, 1.0, rids=[3, 1], finished=0),
           ("ffclock", int(t * MS), 0, 1, {"stamp": int(t * MS)})]
    return ev


def test_iteration_host_on_a_hand_built_timeline(tmp_path):
    run = _run(tmp_path, _timeline())
    its = tickspans.iterations(run.spans)
    assert [(i["prefill"], i["decode"]) for i in its] == [
        (False, True)] * 3 + [(True, True), (False, False), (False, True)]
    assert its[-1]["wall_ns"] is None
    read = _reader("iteration_host").read
    assert read(run, which="decode_only") == pytest.approx(9.0)   # 8, 9, 16
    assert read(run, which="with_prefill") == pytest.approx(24.0)
    with pytest.raises(ValueError):
        read(run, which="neither")


def test_token_gap_on_a_hand_built_timeline(tmp_path):
    run = _run(tmp_path, _timeline())
    read = _reader("token_gap").read
    # commit ends: 119.5, 141.5, 171.5 (1, 2), 181 (3), 211 (1, 2),
    # 231.2 (3, 1): gaps 22, 30, 39.5 twice each for 1 and 2, then 20.2
    # for 1 and 50.2 for 3
    assert read(run, q=50) == pytest.approx(30.0)
    assert read(run, q=100) == pytest.approx(50.2)
    assert read(run, q=0) == pytest.approx(20.2)


# -- joined with the device trace -------------------------------------------

def _planes():
    """Chip 0 busy 0-4, 6-10, 10.01-14, 15-18 and 19-20 ms of a 20 ms
    window; the host in a decode tick whose phases follow one another from
    3 ms on, the fetch ending INSIDE the gap 14-15."""
    dev = [("fusion.1", 0.0, 4 * MS),
           ("ragged_paged_attention.2 [tpu_custom_call]", 6.0 * MS, 4 * MS),
           ("fusion.1", 10.01 * MS, 3.99 * MS),
           ("fusion.3", 15.0 * MS, 3 * MS), ("fusion.3", 19.0 * MS, 1 * MS)]
    host = {"python3": [
        ("decode_tick", 3.0 * MS, 16.8 * MS),
        ("launch_build", 3.0 * MS, 0.5 * MS),
        ("launch_h2d", 3.5 * MS, 1.0 * MS),         # gap 4-6: 0.5 of it here,
        ("launch_dispatch", 4.5 * MS, 1.0 * MS),    # 1.0 here
        ("sample", 5.5 * MS, 1.0 * MS),             # and 0.5 here
        ("fetch", 6.5 * MS, 8.1 * MS),              # gap 14-15: 0.6 here
        ("commit", 14.6 * MS, 5.2 * MS),            # 0.4 here; gap 18-19
        ("np.asarray(jax.Array)", 6.5 * MS, 8.1 * MS)]}
    return {"devices": {0: dev}, "host": host}


def test_idle_by_span_shares_each_gap_out_and_partitions_the_idle_time(
        tmp_path):
    run = _run(tmp_path, planes=_planes())
    mod = _reader("idle_by_span")
    assert mod.read(run, group="launch") == pytest.approx(10.0)   # 2 of 20
    assert mod.read(run, group="fetch") == pytest.approx(3.0)
    assert mod.read(run, group="commit") == pytest.approx(7.0)
    out = run.extras["idle_by_span"]
    assert out["gaps under 20 us"] == pytest.approx(0.05)
    assert sum(out.values()) == pytest.approx(20.05)   # = 100 - busy share
    assert "tick" not in out and "no span" not in out
    with pytest.raises(ValueError):
        mod.read(run, group="tick")
    # idle under the tick span and no phase is the unexplained remainder,
    # idle under no span of ours its own name
    planes = _planes()
    planes["host"]["python3"] = [
        e for e in planes["host"]["python3"] if e[0] != "commit"]
    planes["devices"][0].append(("fusion.9", 21.0 * MS, 1 * MS))
    run = _run(tmp_path, planes=planes)
    assert mod.read(run, group="commit") == 0.0
    out = run.extras["idle_by_span"]            # the window is now 22 ms
    assert out["tick"] == pytest.approx(100 * 1.4 / 22)
    assert out["no span"] == pytest.approx(100 * 1.0 / 22)
    assert out["fetch"] == pytest.approx(100 * 0.6 / 22)


def test_innermost_segments_pick_the_shortest_open_span():
    seg = _reader("idle_by_span").innermost_segments(
        [("tick", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 3.0, 4.0),
         ("inner", 4.0, 1.0), ("other_thread", 20.0, 1.0)])
    assert seg == [(0.0, 1.0, "tick"), (1.0, 3.0, "a"), (3.0, 4.0, "b"),
                   (4.0, 5.0, "inner"), (5.0, 7.0, "b"), (7.0, 10.0, "tick"),
                   (20.0, 21.0, "other_thread")]


def test_span_roofline_arithmetic(tmp_path):
    planes = _planes()
    offset = 7_000 * MS                 # profiler clock - span clock
    planes["host"]["python3"] += [
        (f"ffclock:{int(t * MS - offset)}", t * MS, 0.0) for t in (1, 9, 17)]
    reduction = {"per_chip": {0: {"by_name": {
        "ragged_paged_attention.2 [tpu_custom_call]": 0.004,
        "fusion.1": 0.008}}}}
    # page: 64 rows x 2 kv heads x 128 x (K and V) x 2 bytes = 65536 bytes;
    # a pair: 4 x 4 q heads x 128 = 2048 operations
    spans = [
        _span("launch_dispatch", 4.6 - 7000, 1.0, rows=8, padded_rows=0,
              kv_rows=6000, kv_pages=100, qk_pairs=6000),       # by bytes
        _span("launch_dispatch", 12.0 - 7000, 1.0, rows=512, padded_rows=0,
              kv_rows=2048, kv_pages=32, qk_pairs=2_000_000),   # by operations
        _span("launch_dispatch", 25.0 - 7000, 1.0, rows=8, padded_rows=0,
              kv_rows=1, kv_pages=10 ** 6, qk_pairs=1)]         # after the trace
    run = _run(tmp_path, spans, planes, reduction)
    least = 2 * (100 * 65536 / 819e9 + 2_000_000 * 2048 / 197e12)
    got = _reader("span_roofline").read(run, pattern=KERNEL)
    assert got == pytest.approx(100.0 * least / 0.004, rel=1e-9)
    assert 100 * 65536 / 819e9 > 6000 * 2048 / 197e12      # the first: bytes
    # beacons that do not tie the clocks give nothing, never a guess
    planes["host"]["python3"] = [e for e in planes["host"]["python3"]
                                 if not e[0].startswith("ffclock")]
    assert _reader("span_roofline").read(
        _run(tmp_path, spans, planes, reduction), pattern=KERNEL) is None


@pytest.mark.parametrize("reader,args", [
    ("iteration_host", {"which": "decode_only"}),
    ("token_gap", {"q": 99}),
    ("idle_by_span", {"group": "launch"}),
    ("span_roofline", {"pattern": KERNEL}),
])
def test_a_program_without_the_phases_gives_nothing_to_read(
        tmp_path, reader, args):
    """The parent commit's spans and planes (the six tick spans, commit
    spans of the speculative path without `rids`, no beacon): every new
    reader returns None and raises nothing."""
    spans = [_span("tick_prep", 0, 0.1), _span("decode_tick", 0.2, 18),
             _span("commit", 17, 1.0, emitted=2, accepted=1),
             _span("tick_prep", 20, 0.1), _span("decode_tick", 20.2, 18)]
    planes = _planes()
    planes["host"]["python3"] = [e for e in planes["host"]["python3"]
                                 if e[0] == "decode_tick"]
    reduction = {"per_chip": {0: {"by_name": {
        "ragged_paged_attention.2 [tpu_custom_call]": 0.004}}}}
    run = _run(tmp_path, spans, planes, reduction)
    assert _reader(reader).read(run, **args) is None
    # and with no trace written at all (a CPU run)
    assert _reader(reader).read(_run(tmp_path, spans), **args) is None


# -- the data files and a whole run ------------------------------------------

def test_the_new_metrics_load_in_their_cells():
    cells = spec.load(h.REPO)["cells"]
    for cell, names in NEW.items():
        mine = {m.name: m for m in cells[cell].per_layer}
        assert set(names) <= set(mine)
        for name in names:
            m = mine[name]
            assert m.workloads == (cell,)
            assert m.reader["name"] in ("iteration_host", "token_gap",
                                        "idle_by_span", "span_roofline")
            importlib.import_module("benchmark.readers." + m.reader["name"])
    train = cells["mistral-7b-train4.pretrain-seq4096"]
    assert not {m.name for m in train.per_layer} & {
        n for names in NEW.values() for n in names}


def test_closed_loop_cell_tiny_traced_prints_the_span_metric(
        tmp_path, monkeypatch):
    monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    root = h.make_root(tmp_path)
    cell = spec.load(root)["cells"]["tiny-serve.tiny-closed"]
    assert "host_ms.prefill" in {m.name for m in cell.per_layer}
    res = harness.run_cell(
        cell, seed=2 ** 31 + 5, seconds=3.0, trace=True, root=root,
        t_process_start=time.monotonic(), device=device.attached())
    m = res["metrics"]
    assert res["correct"] and res["attempted"] > 3
    assert m["host_ms.prefill"]["value"] > 0
    assert m["host_ms.prefill"]["unit"] == "ms"
    # wall minus the waits for the device is less than the two tick spans
    assert m["host_ms.prefill"]["value"] < 3000.0
    assert m["step_ms.prefill"]["value"] > 0     # the old metrics still print
    # no TPU plane on the CPU: the device-joined metrics are left out
    for name in ("idle_launch.prefill", "idle_fetch.prefill",
                 "idle_commit.prefill", "ragged_roofline.prefill"):
        assert name not in m
