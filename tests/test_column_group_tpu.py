"""What only the TPU's compiler can show of runtime/column_group.py, shown
without the chip: the train step compiled HERE for a described v5e 2x2
(on-chip-measurement guide, section 2). The CPU compiler merges the
fallback's two reductions by itself; the TPU's does not.

The topology is described inside a fixture, never at import: one worker
loads the TPU's library, the others collect the same tests and skip none."""

import re

import pytest

from flexflow_tpu.models.llama import (
    LlamaConfig,
    build_llama,
    llama_tp_strategy,
)

LCFG = LlamaConfig(vocab_size=2048, dim=512, layers=2, heads=8, kv_heads=4,
                   hidden=1024, rope_theta=1e4)
MESH = {"data": 2, "model": 2}
BATCH, SEQ = 8, 512
X_BYTES = BATCH // MESH["data"] * SEQ * LCFG.dim * 2    # a chip's input


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compiled_step(topo):
    """(optimized HLO, node keys) of the tiny TP x DP train step, traced
    on abstract arguments placed on the described devices (compile() would
    draw weights, and nothing can be put on a described device)."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.parallel.mesh import make_mesh

    ff = FFModel(FFConfig(batch_size=BATCH, seed=0, num_devices=4,
                          mesh_shape=dict(MESH), remat="hidden"))
    build_llama(ff, LCFG, seq_len=SEQ)
    ff._optimizer = AdamOptimizer(lr=1e-4, state_dtype="bfloat16")
    ff._loss_type = LossType.SPARSE_CATEGORICAL_CROSSENTROPY
    ff._metrics = []
    ff.graph.infer_shapes()
    ff._mesh = make_mesh(dict(MESH), list(topo.devices))
    ff._apply_strategy(ff.graph, llama_tp_strategy(LCFG))
    ex = ff._build_executor(ff.graph)
    text = ex.lowered_modules(["train_step"])["train_step"].compile(
    ).as_text()
    return text, [n.stable_key() for n in ff.graph.nodes]


@pytest.fixture(scope="module")
def merged(topo):
    return _compiled_step(topo)


@pytest.fixture(scope="module")
def fallback(topo):
    from flexflow_tpu.runtime import column_group

    mp = pytest.MonkeyPatch()
    mp.setattr(column_group, "column_split",
               lambda graph, mesh, members: None)
    try:
        return _compiled_step(topo)
    finally:
        mp.undo()


def _model_backward(compiled):
    from flexflow_tpu.analysis.hloaudit import parse_hlo_module

    text, keys = compiled
    summary = parse_hlo_module(text, keys, mesh_axes=MESH)
    return [c for c in summary.collectives
            if c.axes == ("model",) and c.phase == "backward"]


def test_the_tpu_compiler_reduces_the_input_gradient_once(merged, fallback):
    mlp = re.compile(r"l\d+_(gate|up)_")
    ours = [c for c in _model_backward(merged) if mlp.match(c.node or "")]
    theirs = [c for c in _model_backward(fallback)
              if mlp.match(c.node or "")]
    # one all-reduce of a chip's input a layer, under `gate`'s key
    assert sorted(c.node.rsplit("_", 1)[0] for c in ours) == [
        f"l{i}_gate" for i in range(LCFG.layers)]
    assert [c.payload for c in ours] == [X_BYTES] * LCFG.layers
    # the fallback moves both partial gradients (at this size the
    # all-reduce combiner makes one call of the two, and may fold a small
    # neighbour in): a layer's input more, and nothing else differs
    assert len(theirs) >= LCFG.layers
    total = lambda cs: sum(c.payload for c in cs)
    assert (total(_model_backward(fallback))
            - total(_model_backward(merged))) == X_BYTES * LCFG.layers


def test_the_merged_reduction_reads_the_activations_dtype(merged):
    text, _keys = merged
    produced = dict(re.findall(
        r"^\s*%([\w.\-]+) = (\w+)\[", text, flags=re.M))
    lines = [ln for ln in text.splitlines()
             if re.search(r"\ball-reduce(-start)?\(", ln)
             and re.search(r"checkpoint/l\d+_gate_\d+/reduce_sum", ln)]
    assert len(lines) == LCFG.layers
    for ln in lines:
        assert re.match(r"\s*%[\w.\-]+ = bf16\[", ln), ln[:200]
        (operand,) = re.search(
            r"all-reduce(?:-start)?\(([^)]*)\)", ln).group(1).split(", ")
        name = operand.split("%")[-1]
        assert produced[name] == "bf16", (operand, produced[name])
