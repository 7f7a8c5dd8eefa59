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
