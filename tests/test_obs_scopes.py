"""The scopes the executor stamps into its train and eval steps, read back
from the optimized HLO through obs.scopes: the one reader of a JAX name
stack. A jax upgrade that renames a transform wrapper fails here, on the
CPU, and is repaired in `scopes.classify` alone."""

import re

import pytest

from flexflow_tpu.obs import scopes

# what jax 0.9.0 writes (CPU lowerings and one TPU trace, PR 38); the node
# in the fourth column is what the stack's SHAPE gives without node keys
NAME_STACKS = [
    ("jit(step)/jvp(forward)/l0_attn_5/dot_general",
     "forward", "l0_attn_5"),
    ("jit(step)/transpose(jvp(forward))/l0_attn_5/transpose",
     "backward", "l0_attn_5"),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "rematted_computation/l0_gate_9/mul", "recompute", "l0_gate_9"),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/l0_gate_9/"
     "dot_general", "backward", "l0_gate_9"),
    # remat="attention": the checkpoint sits INSIDE the node's scope
    ("jit(step)/transpose(jvp(forward))/l0_attn_5/jvp(forward)/l0_attn_5/"
     "checkpoint/rematted_computation/dot_general", "recompute",
     "l0_attn_5"),
    ("jit(step)/jvp(forward)/reduce_max", "forward", None),     # the loss
    ("jit(step)/optimizer/sub", "optimizer", None),
    ("jit(step)/step_metrics/argmax", "step_metrics", None),
    ("jit(step)/forward/l0_attn_5/dot_general", "forward", "l0_attn_5"),
    ("jit(step)/forward/jit(_one_hot)/eq", "forward", None),
    ("jit(step)/jvp(forward)/l0_attn_5/shard_map/pallas_call",
     "forward", "l0_attn_5"),
    # a column-split remat group runs its diamond per shard (PR 39): the
    # map's own scope stands between jax's and the node's
    ("jit(step)/jvp(forward)/shard_map/l0_gate_9/dot_general",
     "forward", "l0_gate_9"),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "rematted_computation/shard_map/l0_gate_9/dot_general",
     "recompute", "l0_gate_9"),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/shard_map/"
     "l0_up_10/dot_general", "backward", "l0_up_10"),
    # the one reduction of the group's input gradient
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/l0_gate_9/"
     "reduce_sum", "backward", "l0_gate_9"),
    ("jit(step)/transpose(jvp())/add_any", None, None),   # a scopeless step
    ("trainable['l0_attn_5']['wq']", None, None),          # a parameter
    ("", None, None),
]


@pytest.mark.parametrize("stack,phase,node", NAME_STACKS,
                         ids=[s[0][-40:] or "empty" for s in NAME_STACKS])
def test_classify_pins_the_name_stacks(stack, phase, node):
    assert scopes.classify(stack) == (phase, node)
    keys = scopes.sorted_keys(["l0_attn_5", "l0_attn_55", "l0_gate_9",
                               "l0_up_10"])
    got_phase, got_node = scopes.classify(stack, keys)
    assert got_phase == phase
    if "[" not in stack:        # a parameter's name holds its node's key
        assert got_node == node


def test_longest_key_wins():
    keys = scopes.sorted_keys(["l0_attn_1", "l0_attn_12"])
    assert scopes.classify("jit(step)/jvp(forward)/l0_attn_12/dot_general",
                           keys) == ("forward", "l0_attn_12")


# what jax 0.9.0 writes into the SERVING step (CPU lowerings of the tiny
# graphs of tests/test_launch_packed.py and tests/test_granite4h.py, and the
# kernels' stacks of a v5e trace, PR 53): (group, node, attention part)
SERVING_STACKS = [
    ("jit(step)/attn/l0_attn_1003/qkv/bse,ef->bsf/dot_general",
     "attn", "l0_attn_1003", "qkv"),
    ("jit(step)/attn/l0_attn_1003/qkv/mul", "attn", "l0_attn_1003", "qkv"),
    ("jit(step)/attn/l0_attn_1003/kv_write/scatter",
     "attn", "l0_attn_1003", "kv_write"),
    ("jit(step)/attn/l0_attn_1003/attend/jit(ragged_flash_attention)/"
     "pallas_call", "attn", "l0_attn_1003", "attend"),
    ("jit(step)/attn/l0_attn_1003/out/bsf,fe->bse/dot_general",
     "attn", "l0_attn_1003", "out"),
    # a node's own constraint, outside its parts
    ("jit(step)/attn/l0_attn_1003/sharding_constraint",
     "attn", "l0_attn_1003", None),
    # a sparse latent layer: the family scopes stay where they were, an
    # operation is its INNERMOST part's
    ("jit(step)/attn/l1_attn_1019/attend/dsa_index/bshd,bnd->bshn/"
     "dot_general", "attn", "l1_attn_1019", "attend"),
    ("jit(step)/attn/l1_attn_1019/attend/dsa_index/kv_write/scatter-add",
     "attn", "l1_attn_1019", "kv_write"),
    ("jit(step)/attn/l1_attn_1019/attend/dsa_select/while/body/add",
     "attn", "l1_attn_1019", "attend"),
    ("jit(step)/attn/l1_attn_1019/dsa_attend/attend/gather",
     "attn", "l1_attn_1019", "attend"),
    ("jit(step)/attn/l1_attn_1019/dsa_attend/kv_write/scatter",
     "attn", "l1_attn_1019", "kv_write"),
    # the group is the node's OpType's: Granite names both mixers alike
    ("jit(step)/attn/l2_mixer_1028/attend/bshd,bthd->bhst/dot_general",
     "attn", "l2_mixer_1028", "attend"),
    ("jit(step)/state/l0_mixer_1004/ssd_scan/while", "state",
     "l0_mixer_1004", None),
    ("jit(step)/state/l0_mixer_1004/ssd_proj/dot_general", "state",
     "l0_mixer_1004", None),
    ("jit(step)/ffn/l0_gate_1006/dot_general", "ffn", "l0_gate_1006", None),
    ("jit(step)/ffn/l0_silu_1008/jit(silu)/logistic", "ffn", "l0_silu_1008",
     None),
    ("jit(step)/experts/l0_moe_1006/dot_general", "experts", "l0_moe_1006",
     None),
    ("jit(step)/head/lm_head_1023/dot_general", "head", "lm_head_1023",
     None),
    ("jit(step)/glue/l0_attn_hc_pre_1003/hc_mix/dot_general", "glue",
     "l0_attn_hc_pre_1003", None),
    ("jit(step)/glue/tok_emb_1001/jit(_take)/gather", "glue", "tok_emb_1001",
     None),
    # the step's own work outside its nodes
    ("jit(step)/unpack/slice", "glue", "unpack", None),
    ("jit(step)/unpack/jit(_where)/select_n", "glue", "unpack", None),
    # a step compiled BEFORE the groups names a node and no group: what a
    # stale compile cache hands back, and a reader reports
    ("jit(step)/l0_attn_1003/dot_general", None, "l0_attn_1003", None),
    ("jit(step)/slice", None, None, None),          # the parent's unpack
    # other programs of a decode tick, a parameter, no stack
    ("jit(_pick)/argmax", None, None, None),
    ("jit(split)/jit(_threefry_split)/threefry2x32", None, None, None),
    ("trainable['l0_attn_1003']['wq']", None, None, None),
    ("", None, None, None),
]


@pytest.mark.parametrize("stack,group,node,part", SERVING_STACKS,
                         ids=[s[0][-44:] or "empty" for s in SERVING_STACKS])
def test_classify_serving_pins_the_name_stacks(stack, group, node, part):
    assert scopes.classify_serving(stack) == (group, node, part)
    # the training reader's view of the same stack is what it was: no
    # phase, since the serving step enters none of the top-level scopes
    assert scopes.classify(stack)[0] is None


def test_the_parts_and_groups_are_the_ones_the_program_names():
    from flexflow_tpu.paged import attention as pa

    assert (pa.QKV, pa.KV_WRITE, pa.ATTEND, pa.OUT) == scopes.ATTN_PARTS
    assert scopes.GROUPS == (scopes.ATTN, scopes.FFN, scopes.EXPERTS,
                             scopes.STATE, scopes.HEAD, scopes.GLUE)
    # no group, part or `unpack` can be mistaken for a node's key, which
    # ends in its guid
    assert not any(re.search(r"_\d+$", s) for s in
                   scopes.GROUPS + scopes.ATTN_PARTS + (scopes.UNPACK,))


GROUPS = [
    ("replica_groups={{0,1},{2,3}}", [[0, 1], [2, 3]], ("model",)),
    ("replica_groups={{0,2},{1,3}}", [[0, 2], [1, 3]], ("data",)),
    ("replica_groups=[2,2]<=[4]", [[0, 1], [2, 3]], ("model",)),
    ("replica_groups=[2,2]<=[2,2]T(1,0)", [[0, 2], [1, 3]], ("data",)),
    ("replica_groups=[1,4]<=[4]", [[0, 1, 2, 3]], ("data", "model")),
    ("replica_groups={{0,1,2,3}}", [[0, 1, 2, 3]], ("data", "model")),
    ("replica_groups={}", [], ("data", "model")),
    ("source_target_pairs={{0,1},{1,0},{2,3},{3,2}}",
     [[0, 1], [1, 0], [2, 3], [3, 2]], ("model",)),
    ("channel_id=3", None, ()),
]


@pytest.mark.parametrize("text,groups,axes", GROUPS,
                         ids=[g[0] for g in GROUPS])
def test_replica_groups_to_mesh_axes(text, groups, axes):
    line = f"%all-reduce.1 = f32[8]{{0}} all-reduce(f32[8]{{0}} %x), {text}"
    assert scopes.collective_groups(line) == groups
    assert scopes.group_axes(groups, {"data": 2, "model": 2}) == axes


def test_iota_groups_over_three_axes():
    # a 2 x 2 x 2 mesh: groups along the middle axis
    line = "replica_groups=[4,2]<=[2,2,2]T(0,2,1)"
    groups = scopes.replica_groups(line)
    assert groups == [[0, 2], [1, 3], [4, 6], [5, 7]]
    mesh = {"data": 2, "seq": 2, "model": 2}
    assert scopes.group_axes(groups, mesh) == ("seq",)
    assert scopes.axes_label(("data", "model")) == "data+model"
    assert scopes.axes_label(()) == "none"


# ---------------------------------------------------------------------------
# the executor's own steps, lowered and compiled on the CPU mesh

STRATEGIES = {
    "tp2_dp2_remat_hidden": ({"data": 2, "model": 2}, True, "hidden"),
    "dp4_remat_attention": ({"data": 4}, False, "attention"),
    "one_chip_no_remat": (None, False, None),
}
# the opcode follows the result type, which ends `]`, `}` or `)`
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_CHECKED = ("dot", "convolution", "custom-call") + _COLLECTIVES


def _build(mesh, tensor_parallel, remat):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.llama import (
        LlamaConfig,
        build_llama,
        llama_tp_strategy,
    )

    lcfg = LlamaConfig.tiny()
    chips = 1
    for size in (mesh or {}).values():
        chips *= size
    ff = FFModel(FFConfig(batch_size=4, seed=0, num_devices=chips,
                          mesh_shape=mesh, remat=remat))
    build_llama(ff, lcfg, seq_len=32)
    ff.compile(optimizer=AdamOptimizer(lr=1e-3, state_dtype="bfloat16"),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=llama_tp_strategy(lcfg) if tensor_parallel else None)
    return ff


@pytest.fixture(scope="module", params=sorted(STRATEGIES))
def lowered(request):
    mesh, tensor_parallel, remat = STRATEGIES[request.param]
    ff = _build(mesh, tensor_parallel, remat)
    mods = ff.executor.lowered_modules(["train_step", "eval_step"])
    return (request.param, ff, mesh or {},
            {k: v.compile().as_text() for k, v in mods.items()})


def _instructions(txt):
    """(opcode, op_name, line) of every instruction that names both."""
    for line in txt.splitlines():
        op, name = _OPCODE.search(line), _OP_NAME.search(line)
        if op and name:
            yield op.group(1).replace("-start", ""), name.group(1), line


def test_every_heavy_instruction_of_the_train_step_has_a_phase(lowered):
    which, ff, _mesh, texts = lowered
    keys = scopes.sorted_keys([n.stable_key() for n in ff.graph.nodes])
    phases, checked = set(), 0
    for opcode, name, line in _instructions(texts["train_step"]):
        phase, node = scopes.classify(name, keys)
        phases.add(phase)
        if opcode in _CHECKED:
            checked += 1
            assert phase in scopes.PHASES, line[:300]
        if phase == scopes.OPTIMIZER:
            assert node is None, line[:300]
        if phase is not None:
            # the stack's shape names the node the keys name
            assert scopes.classify(name)[1] == node, name
    assert checked > 20
    want = {scopes.FORWARD, scopes.BACKWARD, scopes.OPTIMIZER}
    if STRATEGIES[which][2]:
        want.add(scopes.RECOMPUTE)
    assert want <= phases
    if not STRATEGIES[which][2]:
        assert scopes.RECOMPUTE not in phases


def test_the_eval_step_is_forward_and_metrics_only(lowered):
    _which, _ff, _mesh, texts = lowered
    phases = {scopes.classify(name)[0]
              for _op, name, _l in _instructions(texts["eval_step"])}
    assert scopes.FORWARD in phases
    assert phases <= {scopes.FORWARD, scopes.STEP_METRICS, None}


def test_collectives_carry_the_axes_the_strategy_says(lowered):
    from flexflow_tpu.analysis.hloaudit import parse_hlo_module

    which, ff, mesh, texts = lowered
    keys = [n.stable_key() for n in ff.graph.nodes]
    summary = parse_hlo_module(texts["train_step"], keys, mesh_axes=mesh)
    mine = {}
    for opcode, name, line in _instructions(texts["train_step"]):
        if opcode not in _COLLECTIVES or "-done(" in line:
            continue
        axes = scopes.group_axes(scopes.collective_groups(line), mesh)
        label = f"{scopes.axes_label(axes)}/{scopes.classify(name)[0]}"
        mine[label] = mine.get(label, 0) + 1
    by_axes = summary.schedule_by_axes()
    assert {k: v["count"] for k, v in by_axes.items()} == mine
    assert sum(mine.values()) == sum(
        d["count"] for d in summary.schedule().values())
    reduces = [c for c in summary.collectives if c.kind == "all-reduce"]
    if which == "one_chip_no_remat":
        assert not summary.collectives
    elif which == "dp4_remat_attention":
        assert reduces and {c.axes for c in summary.collectives} == {
            ("data",)}
    else:
        assert {c.axes for c in reduces} == {("model",), ("data",)}
        # tensor parallelism: an all-reduce after `wo` and `down` in the
        # forward pass of each of the two layers, none repeated by remat
        forward = [c for c in reduces if c.axes == ("model",)
                   and c.phase == scopes.FORWARD and c.node
                   and re.match(r"l\d+_(attn|down)_", c.node)]
        assert len(forward) == 4
        assert not [c for c in reduces if c.phase == scopes.RECOMPUTE]
        # the gradient sync is the backward pass's, over the data axis
        assert {c.phase for c in reduces if c.axes == ("data",)} == {
            scopes.BACKWARD}


def test_steps_compile_under_a_key_that_holds_their_scopes():
    """A cache warmed by a checkout without the scopes must not hand this
    one its executable: the train and eval steps run under jax's
    metadata-keyed cache context, and nothing else does."""
    import jax

    from flexflow_tpu.runtime.executor import _TracedStep

    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    step = _TracedStep(lambda: seen.append(getattr(jax.config, flag)),
                       "train_step")
    assert getattr(jax.config, flag) is False
    step()
    assert seen == [True] and getattr(jax.config, flag) is False


# ---------------------------------------------------------------------------
# a compiled SERVING module's layout operations (analysis/hloaudit.py), on
# the lines a v5e module of the Mistral-7B cell holds (compiled HERE for the
# described chip, PR 53), cut to what the parse reads

_SERVING_MODULE = """
HloModule jit_step, is_scheduled=true

%fused_computation.5 (param_0.1: bf16[4096,32,128]) -> bf16[4096,4096] {
  %param_0.1 = bf16[4096,32,128]{2,1,0} parameter(0)
  %copy.900 = bf16[4096,32,128]{0,2,1} copy(%param_0.1)
  ROOT %bitcast.9 = bf16[4096,4096]{0,1} bitcast(%copy.900)
}

ENTRY %main.54 (packed.1: s32[15,76], w: bf16[4096,32,128]) -> bf16[15,8] {
  %trainable__l0_attn_1003____wq__.1 = bf16[4096,32,128]{2,1,0} parameter(1), metadata={op_name="trainable['l0_attn_1003']['wq']"}
  %trainable__l0_attn_1003____wo__.1 = bf16[32,128,4096]{2,1,0} parameter(2), metadata={op_name="trainable['l0_attn_1003']['wo']"}
  %copy-start = (bf16[32,128,4096]{2,1,0:S(1)}, bf16[32,128,4096]{2,1,0}, u32[]{:S(2)}) copy-start(%trainable__l0_attn_1003____wo__.1), cross_program_prefetch_index=0
  %bitcast.490 = bf16[4096,4096]{1,0} bitcast(%trainable__l0_attn_1003____wq__.1)
  %bitcast_bitcast_fusion.5 = bf16[4096,4096]{0,1:T(8,128)(2,1)S(1)} fusion(%bitcast.490), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/attn/l0_attn_1003/qkv/bse,ef->bsf/dot_general"}
  %copy.92 = bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} copy(%bitcast_bitcast_fusion.5)
  %fusion.256 = bf16[15,8,32,128]{3,2,1,0} fusion(%copy.92), kind=kOutput, calls=%fused_computation.214, metadata={op_name="jit(step)/attn/l0_attn_1003/qkv/bse,ef->bsf/dot_general"}
  %ragged_paged_attention.12 = bf16[15,8,32,128]{3,2,1,0} custom-call(%fusion.256), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attn/l0_attn_1003/attend/jit(ragged_flash_attention)/pallas_call"}
  %copy-done = bf16[32,128,4096]{2,1,0:S(1)} copy-done(%copy-start)
  %fusion.300 = bf16[15,8,4096]{2,1,0} fusion(%ragged_paged_attention.12, %copy-done), kind=kOutput, calls=%fused_computation.215, metadata={op_name="jit(step)/attn/l0_attn_1003/out/bsf,fe->bse/dot_general"}
  ROOT %slice.7 = bf16[15,8]{1,0} slice(%fusion.300), slice={[0:15], [0:8], [0:1]}, metadata={op_name="jit(step)/head/softmax_1064/slice"}
}
"""


def test_hloaudit_names_a_serving_modules_layout_operations():
    from flexflow_tpu.analysis import hloaudit

    rows = {r["name"]: r for r in
            hloaudit.layout_operations(_SERVING_MODULE)}
    # a fusion's body, a `-done` and a free `bitcast` are not operations
    assert set(rows) == {"copy-start", "bitcast_bitcast_fusion.5",
                         "copy.92", "slice.7"}
    relaid = rows["bitcast_bitcast_fusion.5"]
    assert (relaid["group"], relaid["node"], relaid["part"]) == (
        "attn", "l0_attn_1003", "qkv")
    assert relaid["result"] == "bf16[4096,4096]{0,1:T(8,128)(2,1)S(1)}"
    assert relaid["bytes"] == 4096 * 4096 * 2
    # a copy the compiler made names nothing: its operand's node and part
    assert (rows["copy.92"]["group"], rows["copy.92"]["part"]) == (
        "attn", "qkv")
    assert rows["copy.92"]["operand"] == "%bitcast_bitcast_fusion.5"
    # a weight's prefetch: the parameter's own `op_name` is its path in the
    # arguments and says nothing; the pair is charged to the node whose
    # key the parameter's name holds, and to the part that runs where its
    # `-done` stands (`out`, not the `qkv` that follows its `-start`)
    pre = rows["copy-start"]
    assert (pre["group"], pre["node"], pre["part"]) == (
        "attn", "l0_attn_1003", "out")
    assert rows["slice.7"]["group"] == "head"
