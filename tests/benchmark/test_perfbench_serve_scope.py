"""The reader that splits a traced serving launch's device time by group,
attention part and kind of operation (benchmark/readers/serve_scope.py), on
hand-built events, on a CPU trace (nothing to read) and through the metric
files of the cells that report it."""

import pytest

from benchmark import spec, xplane
from benchmark.readers import scope_share, serve_scope
from benchmark.xplane_stats import StatEvent
from flexflow_tpu.obs import scopes

from test_perfbench_scope_time import DATA, REPO, _place, _Run

MS = 1e6    # an event's times are nanoseconds
CHAT = "mistral-7b-serve1.chat-steady"
BACKLOG = ("mistral-7b-serve1.longdoc-backlog",
           "mistral-small-4-serve1.longctx-backlog",
           "mellum2-12b-serve1.repo-mixed-backlog",
           "ling-3-flash-serve1.hybrid-longctx-backlog",
           "glm-5.3-flash-serve1.sparse-longctx-backlog",
           "granite-4.0-h-micro-serve1.reasoning-decode-backlog")
KERNEL = ('custom-call(bf16[8]{0} %fusion.1), '
          'custom_call_target="tpu_custom_call"')


def _ev(name, start_ms, dur_ms, stack=None):
    stats = {} if stack is None else {"tf_op": stack + ":"}
    return StatEvent(name, start_ms * MS, dur_ms * MS, stats)


def _launch(t0, grouped=True):
    """One decode tick of 12 ms: the step program (10 ms) and four small
    programs behind it. The step holds an attention node's four parts
    (a kernel among them), a state node's loop of 3 ms around two
    operations (nested: they count once), a layout fusion in the
    feed-forward, the head, the descriptor's slices, an asynchronous copy
    whose `-done` names no stack, a stackless copy of an attention
    operand, and a copy of a parameter that nothing names."""
    def s(group, rest):
        return "jit(step)/" + (group + "/" if grouped else "") + rest

    attn = "l0_attn_5/" + ("{}/" if grouped else "")
    step = [
        _ev("%slice.1 = s32[8]{0} slice(s32[8,70]{1,0} %packed)", t0, 0.2,
            "jit(step)/unpack/slice" if grouped else "jit(step)/slice"),
        _ev("%fusion.1 = bf16[8,4096]{1,0} fusion(bf16[8,4096]{1,0} %x), "
            "kind=kOutput", t0 + 0.2, 1.8,
            s("attn", attn.format("qkv") + "dot_general")),
        _ev("%copy.7 = bf16[8,4096]{0,1} copy(bf16[8,4096]{1,0} %fusion.1)",
            t0 + 2.0, 0.4),
        _ev("%scatter.2 = bf16[64,8,1024]{2,1,0} scatter(bf16[64,8,1024]"
            "{2,1,0} %pool)", t0 + 2.4, 0.3,
            s("attn", attn.format("kv_write") + "scatter")),
        _ev("%copy-start.1 = (bf16[64]{0}, bf16[64]{0}, u32[]) copy-start("
            "bf16[64]{0} %scatter.2)", t0 + 2.7, 0.1,
            s("attn", attn.format("attend") + "reshape")),
        _ev("%ragged_paged_attention.3 = bf16[8]{0} " + KERNEL, t0 + 2.8, 1.0,
            s("attn", attn.format("attend") + "pallas_call")),
        _ev("%copy-done.1 = bf16[64]{0} copy-done((bf16[64]{0}, bf16[64]{0}, "
            "u32[]) %copy-start.1)", t0 + 3.8, 0.2),
        _ev("%fusion.4 = bf16[8,4096]{1,0} fusion(bf16[8,4096]{1,0} %o), "
            "kind=kOutput", t0 + 4.0, 0.5,
            s("attn", attn.format("out") + "dot_general")),
        _ev("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
            t0 + 4.5, 3.0, s("state", "l1_mixer_9/ssd_scan/while")),
        _ev("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
            t0 + 4.6, 1.0, s("state", "l1_mixer_9/ssd_scan/while/body/mul")),
        _ev("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop",
            t0 + 5.8, 1.5, s("state", "l1_mixer_9/ssd_scan/while/body/add")),
        _ev("%bitcast_bitcast_fusion.2 = bf16[8,2,2048]{2,1,0} fusion("
            "bf16[8,4096]{1,0} %h), kind=kLoop", t0 + 7.5, 0.5,
            s("ffn", "l1_gate_11/dot_general")),
        _ev("%convolution_convert_fusion.3 = f32[8,32768]{1,0} fusion("
            "bf16[8,4096]{1,0} %n), kind=kOutput", t0 + 8.0, 1.5,
            s("head", "lm_head_30/dot_general")),
        _ev("%copy.9 = f32[4096]{0} copy(f32[4096]{0} %param.3)",
            t0 + 9.5, 0.5),
    ]
    # other programs: instruction names repeat across programs
    rest = [
        _ev("%fusion.1 = f32[8]{0} fusion(f32[8,32768]{1,0} %p), kind=kLoop",
            t0 + 10.2 + 0.4 * i, 0.1, f"jit({name})/argmax")
        for i, name in enumerate(("_last", "split", "_pick", "_set_newest"))]
    modules = [_ev("jit_step(11)", t0, 10.0)] + [
        _ev(f"jit_{name}({7 + i})", t0 + 10.2 + 0.4 * i, 0.1)
        for i, name in enumerate(("_last", "split", "_pick", "_set_newest"))]
    return step + rest, modules


def _two_launches(grouped=True):
    ops, modules = [], []
    for t0 in (0.0, 20.0):
        o, m = _launch(t0, grouped)
        ops += o
        modules += m
    return ops, modules


def test_the_groups_the_other_programs_and_the_rest_add_up_to_busy():
    t = serve_scope.build(*_two_launches(), scopes)
    assert t["step"] == "jit_step" and t["launches"] == 2
    assert t["programs"] / t["launches"] == 5.0
    assert t["busy_ms"] == pytest.approx(10.0 + 0.4)
    g = t["groups"]
    # the loop's 3 ms hold its two operations: 3, not 5.5
    assert g["state"] == pytest.approx(3.0)
    # the attention node: its four parts, the stackless copy of its
    # projection (charged to `qkv`, its operand's part) and the `-done`
    # of an asynchronous copy (its `-start`'s part, `attend`)
    assert g["attn"] == pytest.approx(1.8 + 0.4 + 0.3 + 0.1 + 1.0 + 0.2 + 0.5)
    assert g["ffn"] == pytest.approx(0.5)
    assert g["head"] == pytest.approx(1.5)
    assert g["glue"] == pytest.approx(0.2)
    assert g[serve_scope.OTHER_PROGRAMS] == pytest.approx(0.4)
    # a parameter's copy names nothing and no operand of it does
    assert g[serve_scope.UNSCOPED] == pytest.approx(0.5)
    assert sum(g.values()) == pytest.approx(t["busy_ms"])
    assert t["unscoped_share"] == pytest.approx(100 * 0.5 / 10.4)
    assert t["stale_share"] == 0.0 and t["scoped"]


def test_the_four_parts_add_up_to_the_attention_nodes():
    t = serve_scope.build(*_two_launches(), scopes)
    p = t["parts"]
    assert p == {"qkv": pytest.approx(1.8 + 0.4),
                 "kv_write": pytest.approx(0.3),
                 "attend": pytest.approx(0.1 + 1.0 + 0.2),
                 "out": pytest.approx(0.5)}
    assert sum(p.values()) == pytest.approx(t["groups"]["attn"])
    assert t["partless_ms"] == 0.0
    c = t["cells"]
    assert c["attn", "attend", "kernel"] == pytest.approx(1.0)
    assert c["attn", "attend", "layout"] == pytest.approx(0.3)
    assert c["attn", "qkv", "layout"] == pytest.approx(0.4)
    assert c["attn", "qkv", "other"] == pytest.approx(1.8)


def test_a_weights_prefetch_is_charged_to_the_part_that_reads_it():
    """The compiler prefetches a weight with a stackless `slice-start` of
    the PARAMETER and waits in a `slice-done` just before the first use
    (behind a bitcast the trace never shows): the wait is the node's whose
    key the parameter's name holds, and the part's that runs next."""
    a = "jit(step)/attn/l0_attn_5/"
    ops = [
        _ev("%slice-start.4 = ((bf16[4096,32,128]{2,1,0}), bf16[1024,32,128]"
            "{2,1,0:S(1)}, s32[]{:S(2)}) slice-start(bf16[4096,32,128]"
            "{2,1,0} %trainable__l0_attn_5____wo__.1), slice={[0:1024]}",
            0.0, 0.1),
        _ev("%fusion.1 = bf16[8,4096]{1,0} fusion(bf16[8,4096]{1,0} %x), "
            "kind=kOutput", 0.1, 1.0, a + "qkv/dot_general"),
        _ev("%slice-done.4 = bf16[1024,32,128]{2,1,0:S(1)} slice-done(("
            "(bf16[4096,32,128]{2,1,0}), bf16[1024,32,128]{2,1,0:S(1)}, "
            "s32[]{:S(2)}) %slice-start.4)", 1.1, 0.4),
        _ev("%fusion.4 = bf16[8,4096]{1,0} fusion(bf16[8,4096]{1,0} "
            "%bitcast.9), kind=kOutput", 1.5, 0.5, a + "out/dot_general"),
        # a parameter of a node the trace never names stays unscoped
        _ev("%copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start("
            "f32[8]{0} %trainable__l9_norm_77____scale__.1)", 2.0, 0.2),
    ]
    t = serve_scope.build(ops, [_ev("jit_step(11)", 0.0, 2.5)], scopes)
    # the start ran before `qkv`, the wait before `out`: each its next
    assert t["parts"] == {"qkv": pytest.approx(0.1 + 1.0),
                          "out": pytest.approx(0.4 + 0.5)}
    assert t["cells"]["attn", "out", "layout"] == pytest.approx(0.4)
    assert t["groups"][serve_scope.UNSCOPED] == pytest.approx(0.2)


def test_layout_operations_whatever_their_group():
    t = serve_scope.build(*_two_launches(), scopes)
    # copy, copy-start / -done, the fusion of two bitcasts, the descriptor's
    # slice and the parameter's copy; not the scatter, not a named fusion
    assert t["layout_ms"] == pytest.approx(0.2 + 0.4 + 0.1 + 0.2 + 0.5 + 0.5)
    top = t["layout"][0]
    assert top[2] == ("ffn", None, "gate", "bitcast_bitcast_fusion",
                      "bf16[8,2,2048]{2,1,0} <- bf16[8,4096]{1,0}")
    assert top[1] == pytest.approx(100 * 0.5 / 10.4)
    # one instruction of one program, with the operand it moves
    assert top[3:] == (1, "%h")
    by = {k[:2] + k[3:4]: v for v, _s, k, _n, _o in t["layout"]}
    assert by["attn", "qkv", "copy"] == pytest.approx(0.4)
    assert by[serve_scope.UNSCOPED, None, "copy"] == pytest.approx(0.5)


@pytest.mark.parametrize("line,kind", [
    ("%copy.1 = bf16[8]{0} copy(bf16[8]{0} %a)", "layout"),
    ("%slice-start.2 = (bf16[8]{0}, u32[]) slice-start(bf16[64]{0} %a)",
     "layout"),
    ("%transpose.3 = bf16[8,4]{0,1} transpose(bf16[4,8]{1,0} %a)", "layout"),
    ("%reshape.4 = bf16[32]{0} reshape(bf16[4,8]{1,0} %a)", "layout"),
    ("%copy_bitcast_fusion = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop",
     "layout"),
    ("%bitcast_bitcast_fusion.12 = bf16[8]{0:T(8,128)(2,1)} fusion(bf16[8]"
     "{0} %a), kind=kLoop", "layout"),
    ("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop", "other"),
    ("%multiply_bitcast_fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a)",
     "other"),
    ("%convolution_convert_fusion.1 = f32[8]{0} fusion(bf16[8]{0} %a)",
     "other"),
    ("%scatter.1 = bf16[8]{0} scatter(bf16[8]{0} %a)", "other"),
    ("%mla_paged_attention.2 = bf16[8]{0} " + KERNEL, "kernel"),
], ids=lambda v: v.split(" = ")[0] if " = " in v else v)
def test_the_kind_of_an_operation_is_read_off_its_hlo_line(line, kind):
    from flexflow_tpu.analysis import hloaudit

    assert serve_scope.kind(line) == kind
    # the program's own audit of a compiled module decides the same way
    name = line.split(" = ")[0].lstrip("%")
    assert hloaudit.is_layout(name, serve_scope.opcode(line)) == (
        kind == "layout")
    assert serve_scope.LAYOUT_OPCODES == hloaudit.LAYOUT_OPCODES
    assert serve_scope.LAYOUT_WORDS == hloaudit.LAYOUT_WORDS


def test_a_step_from_before_the_groups_is_reported_and_read_without():
    """What a stale compile cache hands back: the step's operations name
    graph nodes and no group. The split is not read; what needs no scope
    is, and the share that names a node is there to log."""
    t = serve_scope.build(*_two_launches(grouped=False), scopes)
    assert not t["scoped"]
    assert t["stale_share"] > 80.0
    assert t["layout_ms"] == pytest.approx(1.9)
    assert t["programs"] / t["launches"] == 5.0
    # a checkout whose scopes cannot classify a serving stack
    t = serve_scope.build(*_two_launches(), None)
    assert not t["scoped"] and t["stale_share"] == 0.0
    assert t["layout_ms"] == pytest.approx(1.9)


class _Traced(_Run):
    def __init__(self, table):
        self.extras, self.trace = {serve_scope.MEMO: table}, True


def test_every_new_metric_reads_its_number_through_its_file():
    """The 24 metric files, through the reader, on the hand-built launch:
    each cell's `node_ms.*` and the rest add up to busy, the parts to
    `node_ms.attn.*`; a step without the groups leaves the split out."""
    cells = spec.load(REPO)["cells"]
    table = serve_scope.build(*_two_launches(), scopes)
    stale = serve_scope.build(*_two_launches(grouped=False), scopes)
    seen = set()
    for name in (CHAT,) + BACKLOG:
        phase = "decode" if name == CHAT else "prefill"
        mine = {m.name: m for m in cells[name].per_layer
                if m.reader["name"] == "serve_scope"}
        seen |= set(mine)
        assert {f"node_ms.{g}.{phase}" for g in ("attn", "head", "glue")} \
            <= set(mine)
        got, old = {}, {}
        for m in mine.values():
            assert m.source == "device_trace" and m.better == "lower"
            assert m.moves == ("tpot_p90" if name == CHAT else "serve_tok_s")
            assert m.name.endswith("." + phase)
            args = {k: v for k, v in m.reader.items() if k != "name"}
            got[m.name] = serve_scope.read(_Traced(table), **args)
            old[m.name] = serve_scope.read(_Traced(stale), **args)
        assert all(v is not None for v in got.values()), got
        assert got[f"programs_per_launch.{phase}"] == 5.0
        assert sum(got[f"attn_part_ms.{p}.{phase}"]
                   for p in scopes.ATTN_PARTS) == pytest.approx(
                       got[f"node_ms.attn.{phase}"])
        assert {k for k, v in old.items() if v is not None} == {
            f"layout_ms.{phase}", f"programs_per_launch.{phase}"}
    assert len(seen) == 24
    # which cells a group's metric lists: where the graph has such nodes
    has = {g: {c for c in BACKLOG if f"node_ms.{g}.prefill" in {
        m.name for m in cells[c].per_layer}} for g in scopes.GROUPS}
    assert has["attn"] == has["head"] == has["glue"] == set(BACKLOG)
    assert has["ffn"] == {BACKLOG[0], BACKLOG[3], BACKLOG[4], BACKLOG[5]}
    assert has["experts"] == set(BACKLOG[1:5])
    assert has["state"] == set(BACKLOG[3:])
    assert serve_scope.read(_Traced(None), "layout_ms") is None


def test_two_decode_ticks_recorded_on_the_chip(tmp_path):
    """`chat-steady.two-ticks.v5e.xplane.pb.gz`: chip 0's `XLA Modules`
    and `XLA Ops` events of two decode ticks of the cell, statistics kept
    (data/README.chat-steady.two-ticks.txt says how it was cut): the
    stacks as libtpu writes them, the compiler's stackless prefetches of
    parameters, fourteen programs a tick."""
    import os

    cell = spec.load(REPO)["cells"][CHAT]
    run = _Run(cell, _place(tmp_path, os.path.join(
        DATA, "chat-steady.two-ticks.v5e.xplane.pb.gz")), 2)
    got = {}
    for m in cell.per_layer:
        if m.reader["name"] == "serve_scope":
            args = {k: v for k, v in m.reader.items() if k != "name"}
            got[m.name] = serve_scope.read(run, **args)
    assert len(got) == 11 and all(v is not None for v in got.values()), got
    t = run.extras[serve_scope.MEMO]
    assert (t["step"], t["launches"], t["programs"]) == ("jit_step", 2, 28)
    assert got["programs_per_launch.decode"] == 14.0
    # the groups, the other programs and the unscoped rest ARE busy time
    assert sum(t["groups"].values()) == pytest.approx(t["busy_ms"],
                                                      rel=1e-9)
    assert t["busy_ms"] == pytest.approx(4.4222, abs=1e-3)
    assert got["unscoped_share.decode"] < 0.1 and t["stale_share"] == 0.0
    four = sum(got[f"attn_part_ms.{p}.decode"] for p in scopes.ATTN_PARTS)
    assert four == pytest.approx(got["node_ms.attn.decode"], rel=1e-9)
    assert t["partless_ms"] == 0.0
    assert got["node_ms.ffn.decode"] == pytest.approx(2.834, abs=2e-3)
    assert got["node_ms.ffn.decode"] > got["node_ms.attn.decode"] > \
        got["node_ms.head.decode"] > got["node_ms.glue.decode"] > 0.0
    # the kernel is `attend`'s, and what `attn_share.decode` reads of it
    assert t["cells"]["attn", "attend", "kernel"] == pytest.approx(
        0.106, abs=2e-3)
    # S10: the layout operations are the q/k/v weights' re-tiling, six
    # layers' worth, under the attention nodes' `qkv`
    assert got["layout_ms.decode"] == pytest.approx(0.698, abs=2e-3)
    top = t["layout"][0]
    assert top[2][:4] == ("attn", "qkv", "attn", "bitcast_bitcast_fusion")
    assert top[2][4].startswith("bf16[4096,4096]{0,1") and top[3] == 6
    assert t["cells"]["attn", "qkv", "layout"] > 0.9 * got[
        "layout_ms.decode"]
    # the weights' prefetch (`slice-done` of a parameter: no stack of its
    # own) is charged to the node and part that reads the weight
    waits = {k: v for v, k in t["rows"] if k[3] == "slice-done"}
    assert set(waits) == {("attn", "qkv", "attn", "slice-done"),
                          ("attn", "out", "attn", "slice-done")}
    # what the feed-forward's `convolution_convert_fusion` is
    named = {k[3]: k[:3] for _v, k in t["rows"]}
    assert named["convolution_convert_fusion"] == ("ffn", None, "gate")
    # one parse serves `scope_share` too
    assert scope_share.stacks(run) is t["by_stack"]


def test_kda_one_row_share_reads_the_span_every_kda_graph_carries():
    cells = spec.load(REPO)["cells"]
    for name in (BACKLOG[3], BACKLOG[4]):
        m = next(m for m in cells[name].per_layer
                 if m.name == "kda_one_row_share")
        assert m.reader == {"name": "span_counter",
                            "span": "launch_dispatch", "key": "kda_one_row",
                            "over": "kda_pieces", "scale": 100}
        assert m.workloads == (BACKLOG[3], BACKLOG[4])


def test_a_cpu_trace_has_nothing_to_read_and_one_parse_serves_both(
        tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda x: x * 2.0)(jnp.ones((8,))).block_until_ready()
    jax.profiler.stop_trace()
    assert xplane.find_xplane(str(tmp_path))
    cell = spec.load(REPO)["cells"][CHAT]
    run = _Run(cell, str(tmp_path), 3)
    for what in ("node_ms", "attn_part_ms", "layout_ms", "unscoped_share",
                 "programs_per_launch"):
        assert serve_scope.read(run, what, group="attn", part="qkv") is None
    untraced = _Run(cell, str(tmp_path), 3)
    untraced.trace = False
    assert serve_scope.read(untraced, "layout_ms") is None
    # on a plane with events, the reader that parses first hands
    # `scope_share` its {stack: self seconds}
    ops, modules = _two_launches()
    plane = type("Plane", (), {"lines": {xplane.OPS_LINE: ops,
                                         serve_scope.MODULES_LINE: modules}})
    monkeypatch.setattr(serve_scope.xplane_stats, "read_device_planes",
                        lambda path, lines: {0: plane})
    run = _Run(cell, str(tmp_path), 3)
    assert serve_scope.read(run, "programs_per_launch") == 5.0
    by_stack = scope_share.stacks(run)
    assert by_stack["jit(step)/state/l1_mixer_9/ssd_scan/while"] == \
        pytest.approx(2 * 0.5e-3)
    assert scope_share.seconds(run, "^ssd_scan$") == pytest.approx(
        2 * 3.0e-3)
