"""The reader that opens a traced training step by phase, graph node and
mesh axis (benchmark/readers/scope_time.py), on hand-built events, on a CPU
trace (nothing to read) and on a cut of one step recorded on the chip."""

import gzip
import os
import shutil

import pytest

from benchmark import spec, xplane, xplane_stats
from benchmark.readers import scope_time
from benchmark.xplane_stats import StatEvent
from flexflow_tpu.obs import scopes

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "mistral-7b-train4.pretrain-seq4096"
NEW = ("step_device_ms.train", "phase_ms.forward", "phase_ms.recompute",
       "phase_ms.backward", "phase_ms.optimizer", "unscoped_share.train",
       "collective_ms.model", "collective_ms.data", "collective_mb.model",
       "collective_mb.data")
MESH = {"data": 2, "model": 2}
MS = 1e6    # an event's times are nanoseconds


def _ev(name, start_ms, dur_ms, stack=None):
    stats = {} if stack is None else {scope_time.OP_NAME_STAT: stack + ":"}
    return StatEvent(name, start_ms * MS, dur_ms * MS, stats)


def _step(t0, scoped=True):
    """One step of 10 ms: a while loop of 4 ms that holds two operations
    (nested: they count once), two all-reduces over the model axis (one
    group list explicit, one iota), the gradient sync over the data axis,
    one scalar over all four chips, the optimizer, and a copy with no
    name stack."""
    f = "jit(step)/jvp(forward)/" if scoped else "jit(step)/jvp()/"
    b = ("jit(step)/transpose(jvp(forward))/" if scoped
         else "jit(step)/transpose(jvp())/")
    r = (b + "jvp(forward)/checkpoint/rematted_computation/" if scoped
         else b + "jvp()/checkpoint/rematted_computation/")
    o = "jit(step)/optimizer/" if scoped else "jit(step)/"
    return [
        _ev("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
            t0, 4.0, f + "l0_attn_5/while"),
        _ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kOutput",
            t0 + 0.5, 1.0, f + "l0_attn_5/while/body/dot_general"),
        _ev("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b), kind=kLoop",
            t0 + 2.0, 1.5, f + "l0_attn_5/while/body/mul"),
        _ev("%all-reduce.1 = bf16[4,8]{1,0} all-reduce(bf16[4,8]{1,0} %c), "
            "channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add",
            t0 + 4.0, 1.0, f + "l0_attn_5/dot_general"),
        _ev("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %d), kind=kLoop",
            t0 + 5.0, 0.5, r + "l0_gate_9/mul"),
        _ev("%all-reduce.2 = bf16[4,8]{1,0} all-reduce(bf16[4,8]{1,0} %e), "
            "channel_id=2, replica_groups=[2,2]<=[4], to_apply=%add",
            t0 + 5.5, 1.0, b + "l0_gate_9/dot_general"),
        _ev("%all-reduce.3 = f32[256]{0} all-reduce(f32[256]{0} %g), "
            "channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add",
            t0 + 6.5, 2.0, b + "l0_attn_5/reduce_sum"),
        _ev("%all-reduce.4 = f32[]{:T(128)} all-reduce(f32[]{:T(128)} %h), "
            "channel_id=4, replica_groups=[1,4]<=[4], to_apply=%add",
            t0 + 8.5, 0.1, f + "reduce_sum"),
        _ev("%fusion.4 = f32[256]{0} fusion(f32[256]{0} %i), kind=kLoop",
            t0 + 8.6, 0.9, o + "sub"),
        _ev("%copy-start.1 = (bf16[8]{0}, bf16[8]{0}, u32[]) copy-start("
            "bf16[8]{0} %w)", t0 + 9.5, 0.5),
    ]


def _modules(t0s, dur_ms=10.0):
    return [_ev("jit_step(123)", t, dur_ms) for t in t0s] + [
        _ev("jit__threefry_split(7)", t + 10.0, 0.01) for t in t0s]


def test_self_time_counts_nested_operations_once():
    own = scope_time.self_times([(0.0, 10.0), (1.0, 2.0), (4.0, 5.0),
                                 (5.0, 1.0), (10.0, 3.0)])
    assert own == [3.0, 2.0, 4.0, 1.0, 3.0]
    assert sum(own) == 13.0     # the union of the five


def test_table_from_hand_built_events():
    ops = _step(0.0) + _step(20.0)
    t = scope_time.build(ops, _modules((0.0, 20.0)), 2, MESH, scopes)
    assert t["step_module"] == "jit_step(123)"
    assert t["step_device_ms"] == pytest.approx(10.0)
    assert t["busy_ms_a_step"] == pytest.approx(10.0)
    ph = t["phases"]
    # the while loop's 4 ms hold its two operations: 4, not 6.5
    assert ph["forward"] == pytest.approx(4.0 + 1.0 + 0.1)
    assert ph["recompute"] == pytest.approx(0.5)
    assert ph["backward"] == pytest.approx(1.0 + 2.0)
    assert ph["optimizer"] == pytest.approx(0.9)
    assert ph[None] == pytest.approx(0.5)
    assert sum(ph.values()) == pytest.approx(t["busy_ms_a_step"])
    assert t["unscoped_share"] == pytest.approx(5.0)
    co = t["collectives"]
    assert co["model"]["ms"] == pytest.approx(2.0)      # explicit + iota
    assert co["model"]["mb"] == pytest.approx(2 * 4 * 8 * 2 / 1e6)
    assert co["data"]["ms"] == pytest.approx(2.0)
    assert co["data"]["mb"] == pytest.approx(256 * 4 / 1e6)
    assert co["data+model"]["ms"] == pytest.approx(0.1)   # under neither
    assert t["collective_calls"] == {
        ("model", "forward"): 1, ("model", "backward"): 1,
        ("data", "backward"): 1, ("data+model", "forward"): 1}
    rows = {key: ms for ms, key in t["rows"]}
    assert t["rows"][0][1] == ("forward", "attn", "fusion")
    assert rows[("forward", "attn", "fusion")] == pytest.approx(2.5)
    assert rows[("forward", "attn", "while")] == pytest.approx(1.5)
    assert rows[("recompute", "gate", "fusion")] == pytest.approx(0.5)


def test_a_program_without_scopes_has_collectives_and_no_phases():
    ops = _step(0.0, scoped=False)
    t = scope_time.build(ops, _modules((0.0,)), 1, MESH, scopes)
    assert t["phases"] is None
    assert t["collectives"]["model"]["ms"] == pytest.approx(2.0)
    assert t["step_device_ms"] == pytest.approx(10.0)
    # a checkout without obs/scopes.py: the module's time alone
    t = scope_time.build(ops, _modules((0.0,)), 1, MESH, None)
    assert t["phases"] is None and t["collectives"] is None
    assert t["step_device_ms"] == pytest.approx(10.0)
    # one device: nothing crosses an axis
    t = scope_time.build(_step(0.0), _modules((0.0,)), 1, {}, scopes)
    assert t["collectives"] is None and t["phases"] is not None


def test_an_async_pair_is_one_call_on_its_starts_axis():
    """`-done` names no groups: it takes its `-start`'s, moves no bytes of
    its own and is no second call."""
    stack = "jit(step)/transpose(jvp(forward))/l0_up_7/dot_general"
    ops = [
        _ev("%all-reduce-start.1 = f32[256]{0} all-reduce-start(f32[256]{0} "
            "%g), channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), "
            "to_apply=%add", 0.0, 0.5, stack),
        _ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
            0.5, 2.0, stack),
        _ev("%all-reduce-done.1 = f32[256]{0} all-reduce-done(f32[256]{0} "
            "%all-reduce-start.1)", 2.5, 1.5, stack),
    ]
    t = scope_time.build(ops, _modules((0.0,), 4.0), 1, MESH, scopes)
    assert t["collectives"] == {"data": {
        "ms": pytest.approx(2.0), "mb": pytest.approx(256 * 4 / 1e6)}}
    assert t["collective_calls"] == {("data", "backward"): 1}


class _Run:
    """What the reader takes of a harness.Run."""

    def __init__(self, cell, trace_dir, steps):
        self.cell, self._dir, self.trace = cell, trace_dir, True
        self.extras = {"traced_steps": steps}
        self.reduction = None

    def trace_dir(self):
        return self._dir


def _place(tmp_path, xplane_file):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(xplane_file, "rb") as src, open(
            d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def test_the_ten_metrics_load_in_the_train_cell():
    cell = spec.load(REPO)["cells"][CELL]
    mine = {m.name: m for m in cell.per_layer if m.name in NEW}
    assert set(mine) == set(NEW)
    for m in mine.values():
        assert m.reader["name"] == "scope_time"
        assert m.source == "device_trace" and m.moves == "train_tok_s"
        assert m.workloads == (CELL,)
        assert m.layer in ("executor", "device")


def test_statistics_reader_agrees_with_jax_on_names_and_times():
    import jax

    path = os.path.join(DATA, "chat-steady.v5e.xplane.pb")
    mine = xplane_stats.read_device_planes(path)[0].lines[xplane.OPS_LINE]
    data = jax.profiler.ProfileData.from_file(path)
    ref = [ev for plane in data.planes if plane.name == "/device:TPU:0"
           for line in plane.lines for ev in line.events]
    assert len(mine) == len(ref) == 9750
    for a, b in zip(mine, ref):     # jax drops the picoseconds
        assert a.name == b.name
        assert int(a.start_ns) == int(b.start_ns)
        assert int(a.duration_ns) == int(b.duration_ns)
    assert xplane_stats.read_device_planes(path, chips=(1,)) == {}


def test_a_cpu_trace_has_nothing_to_read(tmp_path):
    """The CPU rehearsal cell joins every metric of the train cell: its
    trace has no TPU plane, and every `what` returns None."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda x: x * 2.0)(jnp.ones((8,))).block_until_ready()
    jax.profiler.stop_trace()
    assert xplane.find_xplane(str(tmp_path))
    cell = spec.load(REPO)["cells"][CELL]
    run = _Run(cell, str(tmp_path), 3)
    for m in cell.per_layer:
        if m.name in NEW:
            args = {k: v for k, v in m.reader.items() if k != "name"}
            assert scope_time.read(run, **args) is None
    untraced = _Run(cell, str(tmp_path), 3)
    untraced.trace = False
    assert scope_time.read(untraced, "step_device_ms") is None


def test_one_traced_step_recorded_on_the_chip(tmp_path):
    """`pretrain-seq4096.step.v5e.xplane.pb.gz`: chip 0's `XLA Ops` and
    `XLA Modules` events of ONE traced step of the cell, statistics kept
    (data/README.txt says how it was cut)."""
    cell = spec.load(REPO)["cells"][CELL]
    run = _Run(cell, _place(tmp_path, os.path.join(
        DATA, "pretrain-seq4096.step.v5e.xplane.pb.gz")), 1)
    got = {}
    for m in cell.per_layer:
        if m.name in NEW:
            args = {k: v for k, v in m.reader.items() if k != "name"}
            got[m.name] = scope_time.read(run, **args)
    assert all(v is not None for v in got.values()), got
    t = run.extras[scope_time.MEMO]
    # the phases, the step's metrics and the unscoped rest ARE busy time
    assert sum(t["phases"].values()) == pytest.approx(
        t["busy_ms_a_step"], rel=0.02)
    assert got["unscoped_share.train"] <= 5.0
    assert got["step_device_ms.train"] == pytest.approx(
        t["busy_ms_a_step"], rel=0.02)      # a chip that is never idle
    four = sum(got[f"phase_ms.{p}"] for p in (
        "forward", "recompute", "backward", "optimizer"))
    assert four == pytest.approx(got["step_device_ms.train"], rel=0.06)
    assert got["phase_ms.backward"] > got["phase_ms.forward"] > \
        got["phase_ms.recompute"] > got["phase_ms.optimizer"] > 0.0
    # tensor parallelism: no all-reduce is repeated by the recomputation,
    # and the gradient sync is the backward pass's
    calls = t["collective_calls"]
    assert not [k for k in calls if k[1] == "recompute"]
    assert {p for (a, p) in calls if a == "data"} == {"backward"}
    assert got["collective_mb.model"] > 1000.0
    assert got["collective_mb.data"] > 1000.0
