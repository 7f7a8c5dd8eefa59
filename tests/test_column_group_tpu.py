"""What only the TPU's compiler can show of runtime/column_group.py and of
what a linear's reductions read, shown without the chip: the train step
compiled HERE for a described v5e 2x2 (on-chip-measurement guide, section
2). The CPU compiler merges the fallback's two reductions by itself; the
TPU's does not while they read the dots' float32 partial sums (PRs 38, 39:
the tree's form), and would if they read bfloat16 (PR 42).

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


def _without_the_path(topo):
    from tools.chip_grad_precision import fallback

    with fallback():
        return _compiled_step(topo)


@pytest.fixture(scope="module")
def fallback(topo):
    return _without_the_path(topo)


def _standin_pair(topo, name):
    """(merged, fallback) under one of the gradient tool's stand-ins for a
    linear's three dots, for these two traces."""
    from tools.chip_grad_precision import LOWERINGS

    with LOWERINGS[name]():
        return _compiled_step(topo), _without_the_path(topo)


@pytest.fixture(scope="module")
def float32_pair(topo):
    """As PR 39 knew them: every dot of a linear handing out float32."""
    return _standin_pair(topo, "float32-partials")


@pytest.fixture(scope="module")
def bfloat16_pair(topo):
    """As PR 42 first had them: every dot of a linear handing out the
    activations' dtype."""
    return _standin_pair(topo, "bfloat16-partials")


def _model_backward(compiled):
    from flexflow_tpu.analysis.hloaudit import parse_hlo_module

    text, keys = compiled
    summary = parse_hlo_module(text, keys, mesh_axes=MESH)
    return [c for c in summary.collectives
            if c.axes == ("model",) and c.phase == "backward"]


_MLP = re.compile(r"l\d+_(gate|up)_")


def _total(collectives):
    return sum(c.payload for c in collectives)


def test_the_tpu_compiler_reduces_the_input_gradient_once(
        merged, float32_pair):
    ours = [c for c in _model_backward(merged) if _MLP.match(c.node or "")]
    # one all-reduce of a chip's input a layer, under `gate`'s key
    assert sorted(c.node.rsplit("_", 1)[0] for c in ours) == [
        f"l{i}_gate" for i in range(LCFG.layers)]
    assert [c.payload for c in ours] == [X_BYTES] * LCFG.layers
    # what the map was written against: on float32 partial sums the
    # fallback moves both partial gradients (at this size the all-reduce
    # combiner makes one call of the two, and may fold a small neighbour
    # in): a layer's input more than the map on the same sums, and nothing
    # else differs
    merged32, fallback32 = float32_pair
    theirs = [c for c in _model_backward(fallback32)
              if _MLP.match(c.node or "")]
    assert len(theirs) >= LCFG.layers
    assert (_total(_model_backward(fallback32))
            - _total(_model_backward(merged32))) == X_BYTES * LCFG.layers


def test_the_tree_needs_the_map_as_pr_39_did(merged, fallback):
    """A linear's input gradient hands out float32 (PR 42 kept the
    activations' sums float32 on the link), so the fallback's two
    all-reduces a group still read float32 partial sums and the TPU's
    compiler still leaves them two: a layer's input more over `model`
    going back than the map's program, and nothing else differs."""
    assert (_total(_model_backward(fallback))
            - _total(_model_backward(merged))) == X_BYTES * LCFG.layers


def test_on_bfloat16_partial_sums_the_compiler_merges_the_fallbacks_two(
        bfloat16_pair):
    """Were a linear's dots to hand out the activations' dtype (PR 42's
    first form; the gradient tool's stand-in), the fallback's two
    all-reduces a group would read bfloat16 with no convert folded into
    their output, and the TPU's compiler would make ONE of them by itself
    (`allreduce(a) + allreduce(b)` -> `allreduce(a + b)`): the same calls
    and bytes over `model` going back as the map's program. Not taken:
    rounding the activations' sums before the link moved every gradient
    leaf 1.5-4.9 % farther from float32 (PERF.md section 6)."""
    merged, fallback = bfloat16_pair
    ours, theirs = _model_backward(merged), _model_backward(fallback)
    assert len(theirs) == len(ours)
    assert _total(theirs) == _total(ours)
    mlp = [c for c in theirs if _MLP.match(c.node or "")]
    assert [c.payload for c in mlp] == [X_BYTES] * LCFG.layers


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


# ---------------------------------------------------------------------------
# what every all-reduce of the step READS (PR 42): a linear KERNEL's gradient
# is rounded on the chip before it crosses the batch's axes; a linear's sums
# of ACTIVATIONS cross in float32


_LINE = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<tuple>\(*)(?P<dtype>\w+)\[")
_CALL = re.compile(r" all-reduce(?:-start)?\((.*?)\), channel_id=")
_LINEAR = re.compile(r"^(l\d+_(gate|up|down)|lm_head)_\d+$")
_NORM = re.compile(r"^(l\d+_(attn|mlp)_norm|final_norm)_\d+$")


def _reads(compiled):
    """[(mesh axes, operand dtype, node key or None, the operand's own
    `op_name`)] for every operand of every all-reduce of the program. The
    all-reduce combiner makes one call of many gradients, so what an
    operand is is read from the line that MAKES it, not from the call's."""
    from flexflow_tpu.obs.scopes import (
        classify,
        collective_groups,
        group_axes,
        sorted_keys,
    )

    text, keys = compiled
    keys = sorted_keys(keys)
    made = {}
    for ln in text.splitlines():
        m = _LINE.match(ln)
        if m:
            made[m.group("name")] = (
                "tuple" if m.group("tuple") else m.group("dtype"), ln)

    def op_name(name, hops=4):
        _dtype, ln = made[name]
        found = re.search(r'op_name="([^"]*)"', ln)
        if found or not hops:
            return found.group(1) if found else ""
        inner = re.search(r"\(%([\w.\-]+)", ln)      # a bare bitcast
        return op_name(inner.group(1), hops - 1) if inner else ""

    out = []
    for ln in text.splitlines():
        call = _CALL.search(ln)
        if not call:
            continue
        axes = group_axes(collective_groups(ln), MESH)
        for operand in re.sub(r"/\*index=\d+\*/", "",
                              call.group(1)).split(", "):
            name = operand.split("%")[-1].strip()
            stack = op_name(name)
            out.append((axes, made[name][0], classify(stack, keys)[1],
                        stack))
    return out


def _kind(node):
    """`l1_down_1020` -> `down`, `lm_head_1023` -> `head`."""
    return node.rsplit("_", 1)[0].split("_")[-1]


def test_only_the_named_exceptions_cross_in_float32(merged):
    """Every all-reduce the partitioner places on a linear kernel's
    gradient, like the attention's and the merged reduction, reads
    bfloat16. What still reads float32, each by its node: a linear's sums
    of ACTIVATIONS over `model` (forward `down`, the head's input
    gradient: rounding them first costs every leaf precision, PERF.md
    section 6, PR 42), the embedding table's gradient (a scatter-add into
    the float32 master), the norms' scales, and the loss (its per-token
    sums over the split vocabulary, the label's logit picked across the
    split, its mean)."""
    _text, keys = merged
    reads = _reads(merged)
    assert len(reads) > 8 * LCFG.layers
    assert {dtype for _axes, dtype, _node, _stack in reads} == {
        "bf16", "f32"}
    wide = [(axes, node, stack.rsplit("/", 1)[-1], "transpose(" in stack)
            for axes, dtype, node, stack in reads if dtype == "f32"]
    norms = {k for k in keys if _NORM.match(k)}
    downs = {k for k in keys if re.match(r"l\d+_down_\d+$", k)}
    (table,) = [k for k in keys if k.startswith("tok_emb_")]
    (head,) = [k for k in keys if k.startswith("lm_head_")]
    assert len(norms) == 2 * LCFG.layers + 1 and len(downs) == LCFG.layers
    assert {w for w in wide if w[1]} == (
        {(("data",), table, "scatter-add", True)}
        | {(("data",), k, "reduce_sum", True) for k in norms}
        | {(("model",), k, "dot_general", False) for k in downs}
        | {(("model",), head, "dot_general", True)})
    loss = {(axes, op) for axes, node, op, _back in wide if node is None}
    assert loss == {(("model",), "reduce_sum"), (("model",), "gather"),
                    (("data",), "reduce_sum")}
    # and nothing over the batch's axes under a linear's key
    assert not [r for r in reads if r[0] != ("model",) and r[1] != "bf16"
                and r[2] and _LINEAR.match(r[2])]


def test_every_linear_kernels_gradient_crosses_in_bfloat16(merged):
    """By node and axis: the gradient sync of `gate`, `up` (kernels the
    map is handed at the activations' dtype), `down` and the head over
    `data` reads bfloat16; over `model` only the group's merged input
    gradient does, `down`'s forward partial sums and the head's input
    gradient read float32."""
    _text, keys = merged
    linears = {k for k in keys if _LINEAR.match(k)}
    assert len(linears) == 3 * LCFG.layers + 1
    reads = [r for r in _reads(merged) if r[2] in linears]
    over_data = [r for r in reads if r[0] == ("data",)]
    assert {dtype for _axes, dtype, _node, _stack in over_data} == {"bf16"}
    assert {node for _axes, _dtype, node, _stack in over_data} == linears
    # (the merged sum's operand is made by a fusion that the compiler names
    # for either of the group's two linears)
    over_model = {(_kind(node).replace("gate", "up"), "transpose(" in stack,
                   dtype)
                  for axes, dtype, node, stack in reads
                  if axes == ("model",)}
    assert over_model == {("down", False, "f32"), ("head", True, "f32"),
                          ("up", True, "bf16")}


_CONTRACTION = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)(\[[\d,]*\])\S* convolution\(.*"
    r'op_name="([^"]*)"')


def test_what_a_linears_contractions_hand_out_on_the_tpu(merged):
    """The numerics plan's `accum` (`Executor.dtype_plan()`), read where
    the TPU's module states it: the TPU's compiler makes a `convolution`
    of every dot, and under a LINEAR's key each one that keeps the rows
    (forward, recomputed, the input gradient) hands out float32 and is
    rounded after; the kernel gradient's, and no other, hands out the
    activations' dtype (`ops/jax_ops.py` `contraction`: the chip's own
    accumulator is float32 either way)."""
    from flexflow_tpu.obs.scopes import classify, sorted_keys

    text, keys = merged
    keys = sorted_keys(keys)
    found = set()
    for ln in text.splitlines():
        m = _CONTRACTION.match(ln)
        node = classify(m.group(3), keys)[1] if m else None
        if node and _LINEAR.match(node):
            rows_kept = m.group(2).startswith(f"[{BATCH // MESH['data']},")
            found.add((_kind(node), "transpose(" in m.group(3), rows_kept,
                       m.group(1)))
    assert found == (
        {(k, False, True, "f32") for k in ("gate", "up", "down", "head")}
        | {(k, True, True, "f32") for k in ("gate", "up", "down", "head")}
        | {(k, True, False, "bf16") for k in ("gate", "up", "down", "head")})


def test_the_float32_form_is_what_the_walk_would_catch(float32_pair):
    """The walk sees an all-reduce that reads float32 partial sums: PR
    39's form, reinstated for one trace by the gradient tool's stand-in,
    fails it at every linear's gradient sync."""
    before, _fallback = float32_pair
    wide = {(_kind(node), axes)
            for axes, dtype, node, _stack in _reads(before)
            if dtype == "f32" and node and _LINEAR.match(node)}
    assert wide == {("down", ("model",)), ("head", ("model",)),
                    ("gate", ("data",)), ("up", ("data",)),
                    ("down", ("data",)), ("head", ("data",))}


# ---------------------------------------------------------------------------
# The serving kernels at the shapes of the GLM-5.3-Flash cell (PR 48), in
# THIS file because only one worker may load the TPU's library: the
# largest launch, 72 items of 8 rows, a table of 520 pages of 64 tokens.


def _one_chip(topo, shape, dtype):
    import jax
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(topo.devices[0]))


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense-latent", "sparse-latent"])
def test_the_latent_walk_compiles_for_the_v5e_at_the_cells_shape(
        topo, sparse):
    """`mla_paged_attention` with 64 heads folded over an 8-row window
    against rows of 512 lanes, without and with the rows' verdicts a
    block of four tokens (the masked walk's extra VMEM input)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.paged.latent import latent_flash_attention

    B, W, H, lanes, pages = 72, 8, 64, 512, 520
    args = [_one_chip(topo, (B, W, H, lanes), jnp.bfloat16),
            _one_chip(topo, (4161, 64, lanes), jnp.bfloat16),
            _one_chip(topo, (B, pages), jnp.int32),
            _one_chip(topo, (B,), jnp.int32),
            _one_chip(topo, (B,), jnp.int32),
            _one_chip(topo, (B, W, W), jnp.bool_)]
    if sparse:
        args.append(_one_chip(topo, (B, W, pages * 16), jnp.bool_))

    def walk(q, pool, tables, pos, q_lens, anc, keep=None):
        return latent_flash_attention(q, pool, tables, pos, q_lens, anc,
                                      value_lanes=lanes, block_keep=keep,
                                      block_tokens=4)

    text = jax.jit(walk).lower(*args).compile().as_text()
    assert "mla_paged_attention" in text and "tpu_custom_call" in text


def test_the_scan_compiles_for_the_v5e_at_64_heads(topo):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.pallas import kda_scan

    B, H, d = 72, 64, 128
    flat = _one_chip(topo, (B, kda_scan.ROWS, H * d), jnp.float32)
    item = _one_chip(topo, (B,), jnp.int32)

    def scan(q, k, kb, v, a, state, slots, start, fresh, rows):
        return kda_scan.kda_ragged_scan(q, k, kb, v, a, state, slots, start,
                                        fresh, rows, heads=H)

    text = jax.jit(scan).lower(
        flat, flat, flat, flat, flat,
        _one_chip(topo, (8, H, d, d), jnp.float32), item, item, item,
        item).compile().as_text()
    assert "kda_ragged_scan" in text


def test_the_clamped_expert_kernel_and_the_selection_compile_for_the_v5e(
        topo):
    """The grouped SwiGLU with the clamp in its epilogue at 36 held
    experts of 4096 x 2048, and `select_blocks` at 511 of 8,320 blocks a
    row: a loop of comparisons and counts, no sort."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import latent_attention as la
    from flexflow_tpu.ops.pallas import grouped_experts as ge

    A, G = 576 * 8, 36
    tm = ge.row_tile(A, G, jnp.bfloat16)
    tiles = ge.num_tiles(A, G, tm)
    w = _one_chip(topo, (G, 4096, 2048), jnp.bfloat16)
    text = jax.jit(lambda x, wg, wu, tg, na: ge.grouped_swiglu(
        x, wg, wu, tg, na, tm=tm, limit=10.0)).lower(
        _one_chip(topo, (tiles * tm, 4096), jnp.bfloat16), w, w,
        _one_chip(topo, (tiles,), jnp.int32),
        _one_chip(topo, (1,), jnp.int32)).compile().as_text()
    assert "moe_grouped_swiglu" in text
    text = jax.jit(lambda s, v: la.select_blocks(s, v, 511)).lower(
        _one_chip(topo, (72, 8, 8320), jnp.float32),
        _one_chip(topo, (72, 8, 8320), jnp.bool_)).compile().as_text()
    assert not re.search(r"\bsort\(", text) and "while" in text
