"""A remat="hidden" SwiGLU diamond split by column over one mesh axis runs
per shard (runtime/column_group.py), so that its input gradient crosses
that axis ONCE. The CPU compiler merges the parent's two reductions by
itself, so compiled HLO on the CPU proves nothing: the structure is read
from the step's jaxpr, before XLA's passes. Everything the path does not
take must lower as it did, and train."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from flexflow_tpu import (
    AdamOptimizer,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models.llama import (
    LlamaConfig,
    build_llama,
    llama_tp_strategy,
)
from flexflow_tpu.parallel.sharding import ShardingView
from flexflow_tpu.runtime import column_group

LCFG = LlamaConfig.tiny()
TP_MESHES = {"data2_model2": {"data": 2, "model": 2}, "model2": {"model": 2}}


def _llama(mesh, strategy="tp", remat="hidden", optimizer=None,
           dtype=DataType.BFLOAT16, seed=0):
    chips = int(np.prod(list(mesh.values()))) if mesh else 1
    ff = FFModel(FFConfig(batch_size=4, seed=seed, num_devices=chips,
                          mesh_shape=mesh, remat=remat))
    build_llama(ff, LCFG, seq_len=32, dtype=dtype)
    if strategy == "tp":
        strategy = llama_tp_strategy(LCFG)
    ff.compile(optimizer=optimizer or AdamOptimizer(lr=1e-3),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=strategy)
    return ff


def _batch():
    rs = np.random.RandomState(0)
    x = rs.randint(0, LCFG.vocab_size, (4, 32)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def _step_args(ff):
    x, y = _batch()
    tr, ntr = ff._params
    return tr, ntr, ff._opt_state, jax.random.key(0), y, x


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _step_jaxpr(ff):
    return jax.make_jaxpr(ff.executor.train_step()._fn)(
        *_step_args(ff)).jaxpr


def _maps(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "shard_map"]


def _input_gradient_sums(jaxpr, axis):
    """(sum, map, position) of every sum over a leading dimension that a
    map over `axis` hands out split over `axis`: the one place where a
    group's input gradient crosses it. The partitioner lowers such a sum
    to the shards' own (nothing to add: one row each) and ONE all-reduce."""
    made_by = {id(v): (e, i) for e in _maps(jaxpr)
               for i, v in enumerate(e.outvars)}
    found = []
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name != "reduce_sum" or tuple(
                int(a) for a in eqn.params["axes"]) != (0,):
            continue
        smap, i = made_by.get(id(eqn.invars[0]), (None, None))
        if smap is not None and smap.params["out_specs"][i][0] == axis:
            found.append((eqn, smap, i))
    return found


def _splits(ff):
    ex = ff.executor
    return [column_group.column_split(ex.graph, ex.mesh, g[0])
            for g in ex._remat_groups.values()]


def _without_the_path(monkeypatch):
    monkeypatch.setattr(column_group, "column_split",
                        lambda graph, mesh, members: None)


def _gradients(ff):
    """(loss, {node: {weight: gradient}}) of one SGD step at lr 1."""
    args = _step_args(ff)
    p0 = jax.tree.map(np.asarray, args[0])
    tr, _, _, m = ff.executor.train_step()(*args)
    grads = jax.tree.map(lambda a, b: a - np.asarray(b), p0, tr)
    return float(np.asarray(m["loss"])), grads


# ---------------------------------------------------------------------------
# (i) the structure, before XLA's passes


@pytest.mark.parametrize("mesh", sorted(TP_MESHES))
def test_one_reduction_over_model_for_each_groups_input_gradient(mesh):
    ff = _llama(TP_MESHES[mesh])
    splits = _splits(ff)
    assert len(splits) == LCFG.layers
    for split in splits:
        assert split is not None and split.axis == "model"
        assert [n.name[3:] for n in split.body] == [
            "gate", "up", "silu", "gxu"]
        assert [n.name[3:] for n in split.rest] == ["down"]
    jaxpr = _step_jaxpr(ff)
    sums = _input_gradient_sums(jaxpr, "model")
    # one a group (`down`'s forward reduction stays the partitioner's own
    # and is in no jaxpr), of a chip's whole input at the activations' dtype
    assert len(sums) == LCFG.layers
    for eqn, _smap, _i in sums:
        (operand,) = eqn.invars
        assert operand.aval.shape == (2, 4, 32, LCFG.dim)
        assert operand.aval.dtype == jax.numpy.bfloat16
    # and nothing in the program reduces by hand
    assert not [e for e in _eqns(jaxpr)
                if e.primitive.name.startswith(("psum", "all_reduce"))]
    # a kernel's gradient is summed over the batch's axes the same way:
    # a copy a shard going in, one sum coming back, the partitioner's
    kernels = [e for e, smap, i in _input_gradient_sums(jaxpr, "data")
               if e.invars[0].aval.shape[1:] == (LCFG.dim, LCFG.hidden)]
    assert len(kernels) == (2 * LCFG.layers if "data" in TP_MESHES[mesh]
                            else 0)
    # at the activations' dtype: the map is handed the weights converted,
    # so the float32 master's gradient is converted back AFTER the sum
    assert {str(e.invars[0].aval.dtype) for e in kernels} <= {"bfloat16"}
    for e in kernels:
        user = next(u for u in _eqns(jaxpr)
                    if any(v is e.outvars[0] for v in u.invars))
        assert user.primitive.name == "convert_element_type"
        assert str(user.outvars[0].aval.dtype) == "float32"


@pytest.mark.parametrize("mesh", sorted(TP_MESHES))
def test_the_fallback_lowering_leaves_both_reductions_to_xla(
        mesh, monkeypatch):
    """What the parent did: no map in the step, and for each group two
    transposed dots whose results, each a chip's share of a contraction
    over the split hidden dimension, meet in an `add_any`: the
    partitioner reduces each of them before it."""
    _without_the_path(monkeypatch)
    ff = _llama(TP_MESHES[mesh])
    jaxpr = _step_jaxpr(ff)
    assert _maps(jaxpr) == [] and _input_gradient_sums(jaxpr, "model") == []
    made_by = {id(v): e for e in _eqns(jaxpr) for v in e.outvars}
    pairs = 0
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name != "add_any":
            continue
        roots = []
        for v in eqn.invars:
            e = made_by.get(id(v))
            while e is not None and e.primitive.name in _PASSES_THROUGH:
                e = made_by.get(id(e.invars[0]))
            roots.append(e)
        if all(e is not None and e.primitive.name == "dot_general"
               and e.invars[1].aval.shape == (LCFG.dim, LCFG.hidden)
               for e in roots):
            pairs += 1
    assert pairs == LCFG.layers


_PASSES_THROUGH = ("convert_element_type", "reshape", "transpose",
                   "squeeze", "broadcast_in_dim", "pjit")


def test_the_local_sum_is_what_crosses():
    """The sum's operand leaves the transposed map as the `add_any` of two
    dots against the two kernels: added on the chip, then reduced."""
    ff = _llama(TP_MESHES["data2_model2"])
    sums = _input_gradient_sums(_step_jaxpr(ff), "model")
    assert len(sums) == LCFG.layers
    for _eqn, smap, i in sums:
        inner = smap.params["jaxpr"]
        made_by = {id(v): e for e in _eqns(inner) for v in e.outvars}
        e = made_by[id(inner.outvars[i])]
        while e.primitive.name in _PASSES_THROUGH:
            e = made_by[id(e.invars[0])]
        assert e.primitive.name == "add_any"
        roots = []
        for v in e.invars:
            r = made_by[id(v)]
            while r.primitive.name in _PASSES_THROUGH:
                r = made_by[id(r.invars[0])]
            roots.append(r.primitive.name)
        assert roots == ["dot_general", "dot_general"]


# ---------------------------------------------------------------------------
# (ii) the same numbers


def test_loss_and_gradients_agree_with_the_fallback_and_with_dp(monkeypatch):
    mesh = TP_MESHES["data2_model2"]
    sgd = lambda: SGDOptimizer(lr=1.0)
    loss, grads = _gradients(_llama(mesh, optimizer=sgd(),
                                    dtype=DataType.FLOAT))
    with monkeypatch.context() as m:
        _without_the_path(m)
        loss_fb, grads_fb = _gradients(_llama(mesh, optimizer=sgd(),
                                              dtype=DataType.FLOAT))
    loss_dp, grads_dp = _gradients(_llama({"data": 4}, strategy=None,
                                          optimizer=sgd(),
                                          dtype=DataType.FLOAT))
    for other_loss, other in ((loss_fb, grads_fb), (loss_dp, grads_dp)):
        np.testing.assert_allclose(loss, other_loss, rtol=2e-3)
        flat, tree = jax.tree.flatten(grads)
        flat_other, tree_other = jax.tree.flatten(other)
        assert tree == tree_other
        for a, b in zip(flat, flat_other):
            scale = max(float(np.abs(b).max()), 1e-8)
            np.testing.assert_allclose(a / scale, b / scale,
                                       rtol=2e-3, atol=2e-5)


def _linear_dots(jaxpr):
    """Every `dot_general` traced under a LINEAR node's key: the MLP's
    three a layer and the head, forward, recomputed and transposed."""
    under = re.compile(r"\bl\d+_(gate|up|down)_\d+|\blm_head_\d+")
    return [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"
            and under.search(str(e.source_info.name_stack))]


def _assert_bfloat16_parity(ours, theirs):
    (loss, grads), (loss_other, grads_other) = ours, theirs
    np.testing.assert_allclose(loss, loss_other, rtol=2e-3)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_other)):
        scale = max(float(np.abs(b).max()), 1e-8)
        assert float(np.abs(a - b).max()) / scale < 0.02


def test_bfloat16_gradients_agree_with_the_fallback(monkeypatch):
    """At the cell's dtype each shard's local sum is rounded to bfloat16
    before it crosses, where the fallback rounds after the cross-chip
    sum: parity to bfloat16's reassociation noise here (the bound
    tests/test_perf_features.py gives remat against none); against a
    float32 reference, at the cell's size, on the chip:
    tools/chip_grad_precision.py (PERF.md section 6, PRs 39 and 42)."""
    mesh = TP_MESHES["data2_model2"]
    ours = _gradients(_llama(mesh, optimizer=SGDOptimizer(lr=1.0)))
    _without_the_path(monkeypatch)
    _assert_bfloat16_parity(ours, _gradients(
        _llama(mesh, optimizer=SGDOptimizer(lr=1.0))))


def _kernel_gradient(eqn):
    """Whether a linear's `dot_general` contracts the rows away: its
    result has the kernel's two dimensions, where an activation's keeps
    the rows'."""
    return eqn.outvars[0].aval.ndim == 2


def test_bfloat16_gradients_agree_with_float32_partial_sums():
    """The form before PR 42 (every dot of a linear handing out float32,
    so that every reduction the partitioner placed on one read float32
    partial sums and rounded after the sum; the map handed float32
    kernels), reinstated by the gradient tool's stand-in for one trace:
    the same loss and gradients within the bound above. What rounding a
    group of reductions before the link costs against a float32 reference
    is the chip's to say (tools/chip_grad_precision.py, the columns
    `*_over_float32_partials`; PERF.md section 6, PR 42)."""
    from tools.chip_grad_precision import LOWERINGS

    mesh = TP_MESHES["data2_model2"]
    ours = _llama(mesh, optimizer=SGDOptimizer(lr=1.0))
    theirs = _llama(mesh, optimizer=SGDOptimizer(lr=1.0))
    with LOWERINGS["float32-partials"]():
        jaxpr = _step_jaxpr(theirs)
        before = _gradients(theirs)
    # the stand-in is what it says: float32 out of every linear's dot,
    # float32 kernels into the map, float32 sums of their gradients.
    # A layer: `gate` and `up` run, recomputed and twice transposed,
    # `down` run and twice transposed; the head the same
    assert len(_linear_dots(jaxpr)) == 11 * LCFG.layers + 3
    assert {str(e.outvars[0].aval.dtype)
            for e in _linear_dots(jaxpr)} == {"float32"}
    kernels = [e for e, _smap, _i in _input_gradient_sums(jaxpr, "data")
               if e.invars[0].aval.shape[1:] == (LCFG.dim, LCFG.hidden)]
    assert {str(e.invars[0].aval.dtype) for e in kernels} == {"float32"}
    # and it is gone with the block: the tree's own step traces again, a
    # kernel's gradient at the activations' dtype and nothing else
    dots = _linear_dots(_step_jaxpr(ours))
    assert len(dots) == 11 * LCFG.layers + 3
    assert {(_kernel_gradient(e), str(e.outvars[0].aval.dtype))
            for e in dots} == {(True, "bfloat16"), (False, "float32")}
    assert sum(map(_kernel_gradient, dots)) == 3 * LCFG.layers + 1
    _assert_bfloat16_parity(_gradients(ours), before)


# ---------------------------------------------------------------------------
# (ii') what a linear's dots hand out: the type every reduction the
# partitioner places on one will read, forward and in both transposes


def _linear_jaxprs(dtype, use_bias):
    """The jaxprs of a LINEAR node's lowering and of its gradient with
    respect to input and weights, at activations of `dtype` against
    float32 master weights."""
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.attrs import LinearAttrs
    from flexflow_tpu.ops.registry import LowerCtx, get_lowering

    attrs = LinearAttrs(out_dim=24, use_bias=use_bias)
    lowering = get_lowering(OpType.LINEAR)
    x = jax.numpy.ones((2, 8, 16), dtype)
    params = {"kernel": jax.numpy.ones((16, 24), "float32")}
    if use_bias:
        params["bias"] = jax.numpy.zeros((24,), "float32")

    def forward(x, params):
        (y,) = lowering(attrs, [x], params, LowerCtx())
        return y

    def loss(x, params):
        return forward(x, params).astype("float32").sum()

    return (jax.make_jaxpr(forward)(x, params).jaxpr,
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, params).jaxpr)


def _dots(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("use_bias", [False, True],
                         ids=["no_bias", "bias"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_each_dot_of_a_linear_hands_out_the_type_named_for_it(
        dtype, use_bias):
    """Forward and the input gradient's dot: float32, rounded to the
    activations' dtype AFTER (a sum of activations over a split
    contraction crosses in float32). The kernel gradient's dot: the
    activations' dtype (a shard of the batch's is rounded before the sum
    over the batch's axes). Every one names its type. The float32
    master's gradient is float32: the convert is the `astype`'s
    transpose, after the dot. Under float32 activations nothing is
    narrower than float32 anywhere."""
    forward, backward = _linear_jaxprs(dtype, use_bias)
    (dot,) = _dots(forward)
    back = _dots(backward)
    assert len(back) == 3       # the forward's again, and the two transposes
    for e in [dot] + back:
        assert str(e.outvars[0].aval.dtype) == (
            dtype if _kernel_gradient(e) else "float32"), e
        assert e.params["preferred_element_type"] == e.outvars[0].aval.dtype
    assert sum(map(_kernel_gradient, back)) == 1
    assert str(forward.outvars[0].aval.dtype) == dtype
    assert [str(v.aval.dtype) for v in backward.outvars] == [
        dtype] + ["float32"] * (2 if use_bias else 1)


_NAMED = {"float32": ("float32",) * 3, "activations": (None,) * 3,
          "the_linears": ("float32", "float32", None),
          "the_converse": (None, None, "float32")}


@pytest.mark.parametrize("rank", [2, 3])
@pytest.mark.parametrize("named", sorted(_NAMED))
def test_a_contraction_is_the_product_and_its_gradient(named, rank):
    """`ops.jax_ops.contraction`: whatever its three dots hand out, the
    product and both gradients are `x @ w`'s own (exactly under float32,
    to bfloat16's rounding under bfloat16), each dot's result has the
    type named for it, and the gradients have their arrays' dtypes."""
    from flexflow_tpu.ops.jax_ops import contraction

    rs = np.random.RandomState(3)
    shape = (4, 6, 16)[3 - rank:]
    x32 = jax.numpy.asarray(rs.randn(*shape), "float32")
    w32 = jax.numpy.asarray(rs.randn(16, 24), "float32")
    g32 = jax.numpy.asarray(rs.randn(*shape[:-1], 24), "float32")

    def plain(x, w):
        return ((x @ w) * g32).sum()

    want = (x32 @ w32,) + jax.grad(plain, argnums=(0, 1))(x32, w32)
    for dtype, tol in (("float32", 1e-5), ("bfloat16", 3e-2)):
        x, w = x32.astype(dtype), w32.astype(dtype)
        dot = contraction(*_NAMED[named])

        def ours(x, w):
            return (dot(x, w).astype("float32") * g32).sum()

        got = (dot(x, w),) + jax.grad(ours, argnums=(0, 1))(x, w)
        assert [str(a.dtype) for a in got] == [dtype] * 3
        for a, b in zip(got, want):
            scale = float(np.abs(b).max())
            assert float(np.abs(np.asarray(a, "float32") - b).max()) \
                < tol * scale
        dots = _dots(jax.make_jaxpr(jax.grad(ours, argnums=(0, 1)))(
            x, w).jaxpr)
        assert len(dots) == 3
        forward, input_grad, kernel_grad = _NAMED[named]
        assert [str(e.outvars[0].aval.dtype) for e in dots] == [
            forward or dtype, input_grad or dtype, kernel_grad or dtype]


# ---------------------------------------------------------------------------
# (iii) what the path does not take lowers as before, and trains


def _two_axis_views():
    views = llama_tp_strategy(LCFG)
    both = ("model", "seq")
    hid3 = (("data",), (), both)
    for i in range(LCFG.layers):
        for name in ("gate", "up"):
            views[f"l{i}_{name}"] = ShardingView(
                (hid3,), {"kernel": ((), both)})
        views[f"l{i}_silu"] = ShardingView((hid3,))
        views[f"l{i}_gxu"] = ShardingView((hid3,))
        views[f"l{i}_down"] = ShardingView(
            ((("data",), (), ()),), {"kernel": (both, ())})
    return views


def _mlp(ff, pattern):
    t = ff.create_tensor((4, 64), name="x")
    if pattern == "B":
        t = ff.dense(t, 256, activation="gelu", name="wide")
    else:
        t = ff.gelu(ff.dense(t, 256, name="wide"), name="act")
    return ff.dense(t, 16, name="proj")


def _mlp_views(pattern):
    wide = ShardingView(((("data",), ("model",)),),
                        {"kernel": ((), ("model",)),
                         "bias": (("model",),)})
    views = {"wide": wide,
             "proj": ShardingView(((("data",), ()),),
                                  {"kernel": (("model",), ())})}
    if pattern == "C":
        views["act"] = ShardingView(((("data",), ("model",)),))
    return views


def _fallback_model(case):
    if case in ("pattern_B", "pattern_C"):
        ff = FFModel(FFConfig(batch_size=4, seed=0, num_devices=4,
                              mesh_shape={"data": 2, "model": 2},
                              remat="hidden"))
        _mlp(ff, case[-1])
        ff.compile(optimizer=AdamOptimizer(lr=1e-3),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   strategy=_mlp_views(case[-1]))
        return ff
    mesh, strategy, remat = {
        "seq_parallel": ({"data": 2, "seq": 2, "model": 2},
                         llama_tp_strategy(LCFG, seq_parallel=True),
                         "hidden"),
        "data_only_mesh": ({"data": 4}, "tp", "hidden"),
        "hidden_over_two_axes": ({"data": 2, "seq": 2, "model": 2},
                                 _two_axis_views(), "hidden"),
        "remat_none": ({"data": 2, "model": 2}, "tp", None),
        "one_device": (None, "tp", "hidden"),
    }[case]
    return _llama(mesh, strategy=strategy, remat=remat)


FALLBACKS = ("pattern_B", "pattern_C", "seq_parallel", "data_only_mesh",
             "hidden_over_two_axes", "remat_none", "one_device")


@pytest.mark.parametrize("case", FALLBACKS)
def test_everything_else_lowers_as_before_and_trains(case):
    ff = _fallback_model(case)
    ex = ff.executor
    if case == "remat_none":
        assert ex._remat_groups == {}
    else:
        assert ex._remat_groups
        assert _splits(ff) == [None] * len(ex._remat_groups)
    args = list(_step_args(ff)) if case[:7] != "pattern" else None
    if args is None:
        rs = np.random.RandomState(0)
        tr, ntr = ff._params
        args = [tr, ntr, ff._opt_state, jax.random.key(0),
                rs.randint(0, 16, (4,)).astype(np.int32),
                rs.randn(4, 64).astype(np.float32)]
    step = ex.train_step()
    jaxpr = jax.make_jaxpr(step._fn)(*args).jaxpr
    assert not [m for m in _maps(jaxpr)
                if "pallas" not in str(m.params["jaxpr"])]
    losses = []
    for _ in range(4):
        args[0], args[1], args[2], m = step(*args)
        losses.append(float(np.asarray(m["loss"])))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_a_view_the_path_does_not_understand_falls_back_not_raises():
    """A searched strategy may leave a member without a view, or split the
    input over the axis: None, never an exception."""
    ff = _llama(TP_MESHES["data2_model2"])
    ex = ff.executor
    members = next(iter(ex._remat_groups.values()))[0]
    assert column_group.column_split(ex.graph, ex.mesh, members)
    assert column_group.column_split(ex.graph, None, members) is None
    silu = next(n for n in members if n.name.endswith("_silu"))
    view, silu.sharding = silu.sharding, None
    assert column_group.column_split(ex.graph, ex.mesh, members) is None
    silu.sharding = ShardingView(((("data",), (), ()),))
    assert column_group.column_split(ex.graph, ex.mesh, members) is None
    silu.sharding = view
    norm = ex.graph.node(next(iter(
        ex.graph.in_edges(members[0]))).src)
    kept, norm.sharding = norm.sharding, ShardingView(
        ((("data",), (), ("model",)),))
    assert column_group.column_split(ex.graph, ex.mesh, members) is None
    norm.sharding = kept
    assert column_group.column_split(ex.graph, ex.mesh, members)


# ---------------------------------------------------------------------------
# (iv) names, shapes and shardings stay: a parent's checkpoint loads


def _layout(tree):
    return jax.tree.map(
        lambda a: (a.shape, str(a.dtype), str(a.sharding.spec)), tree)


def test_parameters_state_and_serving_params_keep_their_layout(
        monkeypatch, tmp_path):
    from flexflow_tpu.runtime.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )

    mesh = TP_MESHES["data2_model2"]
    with monkeypatch.context() as m:
        _without_the_path(m)
        parent = _llama(mesh)
        step_args = _step_args(parent)
        tr, ntr, opt, _ = parent.executor.train_step()(*step_args)
        parent._params, parent._opt_state = (tr, ntr), opt
        save_checkpoint(str(tmp_path / "ckpt"), parent)
        want = (_layout(parent._params), _layout(parent._opt_state),
                _layout(parent.serving_params()))
    ff = _llama(mesh, seed=1)
    assert all(_splits(ff))
    assert (_layout(ff._params), _layout(ff._opt_state),
            _layout(ff.serving_params())) == want
    restore_checkpoint(str(tmp_path / "ckpt"), ff)
    for a, b in zip(jax.tree.leaves(ff._params),
                    jax.tree.leaves(parent._params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tr, ntr, opt, m = ff.executor.train_step()(*_step_args(ff))
    assert np.isfinite(float(np.asarray(m["loss"])))
    assert _layout((tr, ntr)) == want[0] and _layout(opt) == want[1]


# ---------------------------------------------------------------------------
# the audit of what is lowered


def test_the_audit_reports_nothing_at_a_merged_group():
    """`CostModel.priced_comm_manifest` prices an input gradient's
    all-reduce at each of the two linears; the merged group lowers one,
    under the first linear's key, and `hloaudit`'s bands take it. (No
    audited baseline compiles with remat="hidden": ROADMAP S4.)"""
    from flexflow_tpu.analysis.hloaudit import diff_entry, parse_hlo_module
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import TPUMachineModel

    mesh = TP_MESHES["data2_model2"]
    ex = _llama(mesh).executor
    cm = CostModel(TPUMachineModel.make("v5e", 4), dict(mesh))
    manifest = cm.priced_comm_manifest(ex.graph, None, training=True)
    text = ex.lowered_modules(["train_step"])["train_step"].compile(
    ).as_text()
    summary = parse_hlo_module(
        text, [n.stable_key() for n in ex.graph.nodes], mesh_axes=mesh)
    mlp = re.compile(r"l\d+_(gate|up|silu|gxu|down)_")
    bad = [f for f in diff_entry("tp", "train_step", manifest, summary)
           if f.severity != "info" and mlp.search(f.where)]
    assert bad == []
    # what is lowered at the group: one reduction over `model` going back
    back = [c for c in summary.collectives if c.axes == ("model",)
            and c.phase == "backward" and c.node and mlp.match(c.node)]
    assert sorted(c.node.rsplit("_", 1)[0] for c in back) == [
        f"l{i}_gate" for i in range(LCFG.layers)]


# ---------------------------------------------------------------------------
# what the merged reduction rounds, measured: the tool the chip runs


def test_the_gradient_precision_tool_compares_every_leaf(tmp_path):
    """`tools/chip_grad_precision.py --tiny`: the step's gradient under
    this lowering, under the fallback and under the tool's stand-ins,
    which round ONE group of a linear's reductions before the link at a
    time, none (PR 39's form, float32-partials) or both; each leaf
    against the plain float32 reference's (on the chip at the train
    cell's size: PERF.md section 6, PRs 39 and 42)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "grad_precision.json"
    done = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "chip_grad_precision.py"),
         "--tiny", "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    names = ["merged", "fallback", "float32-partials",
             "bfloat16-activations", "bfloat16-kernel-gradients",
             "bfloat16-partials"]
    for lowering in names:
        assert f"{lowering}: all-reduce" in done.stdout
    assert "merged / float32-partials error, over 21 leaves" in done.stdout
    doc = json.loads(out.read_text())
    fields = [n.replace("-", "_") for n in names]
    assert doc["columns"] == (
        ["leaf", "elements", "ref_norm"] + [f"{f}_err" for f in fields]
        + [f"{f}_over_float32_partials" for f in fields
           if f != "float32_partials"]
        + [f"merged_minus_{f}" for f in fields[1:]])
    rows = {r[0]: dict(zip(doc["columns"], r)) for r in doc["rows"]}
    assert len(rows) == 3 + 9 * 2       # embed, final norm, head, 2 layers
    assert set(doc["losses"]) == set(names) | {"reference"}
    assert set(doc["rounded_once"]) == {"forward", "kernel_gradient"}
    for name in names:
        assert abs(doc["losses"][name] - doc["losses"]["reference"]) < 2e-3
    kernels = re.compile(r"\.(gate|up|down|head)$")
    for leaf, r in rows.items():
        merged, fallback = r["merged_err"], r["fallback_err"]
        assert 0 < merged < 0.05 and 0 < fallback < 0.05
        assert 0.8 < merged / fallback < 1.25
        assert r["merged_minus_fallback"] <= merged + fallback
        for f in fields:
            assert 0 < r[f"{f}_err"] < 0.05
            if f != "float32_partials":
                # the column a group is judged by: two errors' quotient
                assert r[f"{f}_over_float32_partials"] == pytest.approx(
                    r[f"{f}_err"] / r["float32_partials_err"])
                assert 0.8 < r[f"{f}_over_float32_partials"] < 1.25
        # the tree IS the stand-in that rounds the kernels' gradients
        # alone: the same gradient to the bit; against float32-partials it
        # differs in the linears' own kernels and in no other leaf
        assert r["merged_minus_bfloat16_kernel_gradients"] == 0.0
        assert (r["merged_minus_float32_partials"] > 0) == bool(
            kernels.search(leaf)), leaf
        # rounding the activations' sums moves every leaf
        assert r["merged_minus_bfloat16_activations"] > 0
    # nothing after the last group's backward differs from the fallback
    assert [rows[k]["merged_minus_fallback"] for k in (
        ".layers[1].down", ".final_norm", ".head")] == [0.0, 0.0, 0.0]
