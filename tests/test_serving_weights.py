"""A server streams its weights at the width they are declared
(runtime/serving_weights.py, FFModel.serving_params).

A model keeps float32 masters of the weights it declares bfloat16; a
server launches with a tree in which every leaf its steps only convert
to the declared dtype is stored at that dtype, converted once. These
tests hold the tree to the SAME arithmetic (bitwise, with norm scales
that bfloat16 cannot represent), to adapting by what it can observe
(stored against declared dtype, the steps' jaxprs: no option, no name),
to living with the model's current weights, and to its counters.
"""

import gc
import json
import os
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.readers import span_counter
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, obs
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2
from flexflow_tpu.models.llama import LlamaConfig, build_llama
from flexflow_tpu.models.mistral4 import Mistral4Config, build_mistral4
from flexflow_tpu.models.mixtral import MixtralConfig, build_mixtral
from flexflow_tpu.runtime import serving_weights

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
SLOTS, COLS, PAGE = 2, 3, 8


def _build(family="llama", seed=3, **config):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1, **config))
    if family == "llama":
        build_llama(ff, LlamaConfig.tiny(vocab=VOCAB), seq_len=16)
    elif family == "llama-f32":
        build_llama(ff, LlamaConfig.tiny(vocab=VOCAB), seq_len=16,
                    dtype=DataType.FLOAT)
    elif family == "mixtral":
        build_mixtral(ff, MixtralConfig.tiny(vocab=VOCAB), seq_len=16)
    elif family == "gpt2":
        build_gpt2(ff, GPT2Config.tiny(vocab=VOCAB), seq_len=16)
    elif family == "mistral4":
        build_mistral4(ff, Mistral4Config.tiny(vocab=VOCAB), batch_size=1,
                       seq_len=16)
    ff.compile(optimizer=AdamOptimizer(lr=1e-2),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _roughen(ff, seed=0):
    """Every vector leaf (norm scales, biases) random and NOT
    representable in bfloat16: a tree that narrowed one would not
    serve the same logits."""
    rng = np.random.default_rng(seed)
    for tree in ff._params:
        for node in tree.values():
            for name, leaf in node.items():
                if leaf.ndim == 1:
                    value = 1.0 + 0.37 * rng.standard_normal(leaf.shape)
                    node[name] = jax.device_put(
                        value.astype(np.float32).astype(leaf.dtype),
                        leaf.sharding)
    return ff


def _leaves(ff):
    return (jax.tree_util.tree_flatten_with_path(ff._params)[0],
            jax.tree.leaves(ff.serving_params()))


@pytest.fixture(scope="module")
def models():
    return {f: _roughen(_build(f))
            for f in ("llama", "mixtral", "gpt2", "mistral4")}


def _ragged_probs(ff, params, window):
    ex = ff.executor
    caches = ex.init_paged_kv_cache(1 + SLOTS * COLS, PAGE)
    tables = jnp.asarray(1 + np.arange(SLOTS * COLS, dtype=np.int32)
                         .reshape(SLOTS, COLS))
    pos = jnp.asarray(np.array([3, 0], np.int32))
    q_lens = jnp.asarray(np.array([window, max(1, window - 1)], np.int32))
    deps = jnp.broadcast_to(jnp.arange(window, dtype=jnp.int32),
                            (SLOTS, window))
    anc = jnp.broadcast_to(jnp.tril(jnp.ones((window, window), jnp.bool_)),
                           (SLOTS, window, window))
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, VOCAB, (SLOTS, window)).astype(np.int32))
    probs, _ = ex.ragged_step_fn()(*params, caches, tables, pos, q_lens,
                                   deps, anc, ids)
    return [np.asarray(probs)]


def _dense_probs(ff, params, _window):
    ex = ff.executor
    caches = ex.init_kv_cache(2, 16)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, VOCAB, (2, 5)).astype(np.int32))
    prefill, caches = ex.decode_fn()(*params, caches, 0, ids)
    step, _ = ex.decode_fn()(
        *params, caches, jnp.asarray(np.array([5, 5], np.int32)),
        ids[:, :1])
    return [np.asarray(prefill), np.asarray(step)]


STEPS = [(f, s) for f in ("llama", "mixtral", "gpt2", "mistral4")
         for s in ("ragged-prefill", "ragged-decode", "dense")
         if (f, s) != ("mistral4", "dense")]    # a latent pool is paged only


@pytest.mark.parametrize("family,step", STEPS,
                         ids=[f"{f}-{s}" for f, s in STEPS])
def test_step_probs_bitwise_equal_from_masters_and_served(models, family,
                                                          step):
    """The same arithmetic: bfloat16(master) computed once is what the
    launch computed each time, and a leaf the step reads in float32
    stays float32, so the probabilities are equal bit for bit."""
    ff = models[family]
    run, window = {"ragged-prefill": (_ragged_probs, 4),
                   "ragged-decode": (_ragged_probs, 1),
                   "dense": (_dense_probs, 0)}[step]
    for a, b in zip(run(ff, ff._params, window),
                    run(ff, ff.serving_params(), window)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), \
            float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())


@pytest.mark.parametrize("family", ["llama", "mixtral", "mistral4"])
def test_which_leaves_a_server_holds_narrower(models, family):
    """Every leaf declared bfloat16 that the steps only convert is held
    bfloat16 (the matmul operands AND the embedding table, gathered then
    converted); what a step reads in float32 (norm scales, mistral4's
    router) is the model's own float32 array. The masters are
    untouched."""
    ff = models[family]
    masters, served = _leaves(ff)
    cast = [(jax.tree_util.keystr(p), m, s)
            for (p, m), s in zip(masters, served) if m is not s]
    kept = [m for (_p, m), s in zip(masters, served) if m is s]
    assert cast and kept
    assert all(m.dtype == jnp.float32 for _p, m in masters)
    assert all(s.dtype == jnp.bfloat16 and s.shape == m.shape
               for _k, m, s in cast)
    assert all(np.array_equal(np.asarray(s),
                              np.asarray(m.astype(jnp.bfloat16)))
               for _k, m, s in cast)
    # every matrix went narrow but a router read in float32
    wide = [m for m in kept if m.ndim >= 2]
    assert len(wide) == (2 if family == "mistral4" else 0), \
        [m.shape for m in wide]
    table = next(s for (p, _m), s in zip(masters, served)
                 if s.shape == (VOCAB, 64) and "emb" in
                 jax.tree_util.keystr(p))
    assert table.dtype == jnp.bfloat16


def _fp8_leaf():
    ff = _build("llama")
    node = next(n for n in ff._params[0].values() if "kernel" in n)
    node["kernel"] = node["kernel"].astype(jnp.float8_e4m3fn)
    return ff


STORED = {
    "bfloat16": lambda: _build("llama", weight_dtype="bfloat16"),
    "int8": lambda: _build("llama", weight_dtype="int8"),
    "fp8-model": lambda: _build("llama", weight_dtype="fp8"),
    "float32-declared": lambda: _build("llama-f32"),
    "gpt2-float32-declared": lambda: _build("gpt2"),
}


@pytest.mark.parametrize("how", sorted(STORED))
def test_leaves_stored_as_declared_or_narrower_are_the_models_own(how):
    """It adapts by what it can observe: a model stored at its declared
    width (FFConfig.weight_dtype bfloat16, int8's snapped bfloat16), one
    stored narrower (fp8) and one that declares float32 get their OWN
    tree back, leaf for leaf, and no step is traced for them."""
    ff = STORED[how]()
    assert ff.serving_params() is ff._params
    assert ff.executor.served_dtypes_memo == {}
    server = ff.serve_generation(paged=True, slots=2, max_len=16,
                                 page_size=PAGE)
    try:
        assert server._params is ff._params
        w = server.metrics()["weights"]
        assert w["leaves_cast"] == 0
        assert w["bytes_served"] == w["bytes_master"]
    finally:
        server.stop()


def test_an_fp8_leaf_beside_float32_masters_is_never_widened():
    """Leaf by leaf: the float32 masters beside it go to bfloat16, the
    leaf stored narrower than declared is the model's own array."""
    ff = _fp8_leaf()
    masters, served = _leaves(ff)
    pairs = [(m, s) for (_p, m), s in zip(masters, served)]
    fp8 = [(m, s) for m, s in pairs if m.dtype == jnp.float8_e4m3fn]
    assert len(fp8) == 1 and fp8[0][0] is fp8[0][1]
    assert any(s.dtype == jnp.bfloat16 and m.dtype == jnp.float32
               for m, s in pairs)
    assert not any(s.dtype.itemsize > m.dtype.itemsize for m, s in pairs)


def _qualifies(fn, *avals, declared=jnp.bfloat16) -> bool:
    """Whether argument 0 of `fn` may be stored at `declared`."""
    jaxpr = jax.jit(fn).trace(*avals).jaxpr.jaxpr
    return serving_weights._qualifying(
        jaxpr, {0: jnp.dtype(declared)}) == {0}


W = jax.ShapeDtypeStruct((8, 4), jnp.float32)
X = jax.ShapeDtypeStruct((2, 8), jnp.bfloat16)
IDS = jax.ShapeDtypeStruct((2, 3), jnp.int32)
USES = {
    # every use a convert to the declared dtype: qualifies
    "convert": (lambda w, x: x @ w.astype(x.dtype), True),
    "reshape-then-convert":
        (lambda w, x: x @ w.reshape(2, 4, 4).reshape(8, 4).astype(x.dtype),
         True),
    "gather-then-convert":
        (lambda w, ids: jnp.take(w, ids, axis=0).astype(jnp.bfloat16), True),
    "scan-slices":
        (lambda w, x: jax.lax.scan(
            lambda c, row: (c + row.astype(x.dtype).sum(), None),
            jnp.zeros((), x.dtype), w)[0], True),
    "remat":
        (lambda w, x: jax.checkpoint(
            lambda a, b: b @ a.astype(b.dtype))(w, x), True),
    # a second, float32 use: the leaf stays as stored
    "also-read-wide":
        (lambda w, x: (x @ w.astype(x.dtype)).sum() + w.sum(), False),
    "read-wide-only": (lambda w, x: x.astype(jnp.float32) @ w, False),
    "summed-before-convert":
        (lambda w, ids: jnp.take(w, ids, axis=0).sum(-2)
         .astype(jnp.bfloat16), False),
    "converted-to-another-dtype":
        (lambda w, x: x @ w.astype(jnp.float16).astype(x.dtype), False),
    "handed-back": (lambda w, x: (x @ w.astype(x.dtype), w), False),
    "scan-carry":
        (lambda w, x: jax.lax.scan(
            lambda c, _: (c * 2.0, c.astype(x.dtype).sum()), w,
            None, length=2)[1], False),
    "unknown-consumer":
        (lambda w, x: jax.lax.while_loop(
            lambda s: s[0] < 2,
            lambda s: (s[0] + 1, s[1] + w.astype(x.dtype).sum()),
            (0, jnp.zeros((), x.dtype)))[1], False),
}


@pytest.mark.parametrize("use", sorted(USES))
def test_a_leaf_qualifies_only_if_every_use_converts_it(use):
    """The walker on small programs: a leaf qualifies when each consumer
    is a convert to its declared dtype, reached directly, through moves
    of elements, or inside a call or a scan's read-only operands; any
    other consumer, a convert elsewhere, a carry, or leaving the
    program unconverted disqualifies it. What it does not know it
    refuses."""
    fn, qualifies = USES[use]
    second = IDS if "ids" in fn.__code__.co_varnames else X
    assert _qualifies(fn, W, second) == qualifies


def test_embedding_summed_in_float32_keeps_its_table():
    """From the program, not the WeightSpec: a table declared bfloat16
    whose rows are SUMMED in float32 before the convert is not the same
    arithmetic narrowed first, and stays float32."""
    from flexflow_tpu.ffconst import AggrMode

    ff = FFModel(FFConfig(batch_size=1, seed=1, num_devices=1))
    ids = ff.create_tensor((1, 4, 3), DataType.INT32, name="ids")
    h = ff.embedding(ids, VOCAB, 16, aggr=AggrMode.SUM,
                     dtype=DataType.BFLOAT16, name="emb")
    ff.dense(h, 8, name="out")
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    assert ff.serving_params() is ff._params     # no serving step at all


def test_paged_server_tokens_equal_generate(models):
    """End to end: a paged server on the served tree emits ff.generate's
    tokens (which runs the masters) over a few requests."""
    ff = models["llama"]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, (n,)).astype(np.int32)
               for n in (3, 9, 5)]
    server = ff.serve_generation(paged=True, slots=2, max_len=16,
                                 page_size=PAGE)
    try:
        assert server._params is ff.serving_params()
        assert server._params is not ff._params
        for p in prompts:
            want = ff.generate(p[None, :], 6)[0]
            got = server.generate(p, 6)
            assert np.array_equal(got, want), (got, want)
    finally:
        server.stop()


def test_dense_server_tokens_equal_generate(models):
    ff = models["llama"]
    p = np.random.default_rng(8).integers(1, VOCAB, (6,)).astype(np.int32)
    server = ff.serve_generation(slots=2, max_len=16)
    try:
        assert server._params is ff.serving_params()
        assert np.array_equal(server.generate(p, 5),
                              ff.generate(p[None, :], 5)[0])
        w = server.metrics()["weights"]
        assert w["bytes_served"] < w["bytes_master"]
    finally:
        server.stop()


def test_servers_share_the_tree_and_follow_the_models_weights():
    """Two servers of one model hold the same tree; once the model's
    weights are replaced (fit, a checkpoint load, set_weight) the next
    server serves the new ones, and the memo does not keep the old tree
    alive. ff._params keeps its float32 masters throughout, and fit()
    after serving trains from them."""
    ff = _roughen(_build("llama"))
    prompt = np.arange(1, 7, dtype=np.int32)
    a = ff.serve_generation(paged=True, slots=2, max_len=16,
                            page_size=PAGE)
    b = ff.serve_generation(paged=True, slots=2, max_len=16,
                            page_size=PAGE, prefix_cache=True)
    c = ff.serve_generation(slots=2, max_len=16)
    assert a._params is b._params is c._params is ff.serving_params()
    before = a.generate(prompt, 6)
    old_leaf = weakref.ref(next(
        s for m, s in zip(jax.tree.leaves(ff._params),
                          jax.tree.leaves(a._params)) if m is not s))
    for s in (a, b, c):
        s.stop()
    del a, b, c
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(ff._params))

    x = np.random.default_rng(0).integers(0, VOCAB, (4, 16)).astype(np.int32)
    masters = [np.asarray(m) for m in jax.tree.leaves(ff._params)]
    ff.fit(x, np.roll(x, -1, axis=1), epochs=3, batch_size=1, verbose=False)
    trained = jax.tree.leaves(ff._params)
    assert all(m.dtype == jnp.float32 for m in trained)
    assert any(not np.array_equal(np.asarray(t), m)
               for t, m in zip(trained, masters))

    new = ff.serve_generation(paged=True, slots=2, max_len=16,
                              page_size=PAGE)
    try:
        for m, s in zip(trained, jax.tree.leaves(new._params)):
            assert np.array_equal(np.asarray(s),
                                  np.asarray(m.astype(s.dtype)))
        after = new.generate(prompt, 6)
        assert np.array_equal(after, ff.generate(prompt[None, :], 6)[0])
        gc.collect()
        assert old_leaf() is None, "the memo kept the old tree alive"
        # one leaf replaced in place: the tuple is the same object, the
        # tree is not
        ff.set_weight("lm_head", ff.get_weight("lm_head") * 0.5)
        assert ff.serving_params() is not new._params
    finally:
        new.stop()
    assert before.shape == after.shape


def test_weight_bytes_on_metrics_and_launch_span(models):
    """server.metrics()["weights"] and the launch_dispatch span's
    weight_bytes read the bytes of the leaves handed to the launch; the
    benchmark's reader takes the newest span's."""
    ff = models["llama"]
    served = jax.tree.leaves(ff.serving_params())
    masters = jax.tree.leaves(ff._params)
    server = ff.serve_generation(paged=True, slots=2, max_len=16,
                                 page_size=PAGE)
    rec = obs.enable()
    try:
        server.generate(np.arange(1, 10, dtype=np.int32), 4)
        w = server.metrics()["weights"]
    finally:
        obs.disable()
        server.stop()
    assert w == {
        "bytes_master": sum(x.nbytes for x in masters),
        "bytes_served": sum(x.nbytes for x in served),
        "leaves_cast": sum(m is not s for m, s in zip(masters, served)),
    }
    assert w["bytes_served"] < w["bytes_master"] < 2 * w["bytes_served"]
    spans = [ev for ev in rec.events if ev[0] == "launch_dispatch"]
    assert spans and all(ev[4]["weight_bytes"] == w["bytes_served"]
                         for ev in spans)
    for phase in ("prefill", "decode"):
        with open(os.path.join(
                REPO, "benchmark", "metrics",
                f"weight_bytes_per_launch.{phase}.json")) as f:
            reader = json.load(f)["reader"]
        assert reader.pop("name") == "span_counter"
        assert span_counter.read(types.SimpleNamespace(spans=rec.events),
                                 **reader) == float(w["bytes_served"])
    # a program without the attribute (the parent's): the metric is left out
    bare = [ev[:4] + ({k: v for k, v in ev[4].items()
                       if k != "weight_bytes"},) + ev[5:]
            for ev in spans]
    assert span_counter.read(types.SimpleNamespace(spans=bare),
                             **reader) is None


def test_audit_lowers_the_paged_entries_against_the_served_tree(models):
    """lowered_modules() and dtype_plan() audit what a server launches:
    the paged entries take the served tree's dtypes (bfloat16 weights
    for a llama), train and eval the float32 masters."""
    ex = models["llama"].executor

    def arguments(lowered):
        text = lowered.as_text()
        head = text[text.index("func.func public @main"):]
        return head[:head.index("->")]

    lows = ex.lowered_modules(["paged_decode", "verify", "eval_step"],
                              slots=2, max_nodes=4)
    for entry in ("paged_decode", "verify"):
        head = arguments(lows[entry])
        assert f"tensor<{VOCAB}x64xbf16>" in head, entry     # the table
        assert "tensor<64x128xbf16>" in head, entry          # a projection
        assert "tensor<64xf32>" in head, entry               # a norm scale
        assert "tensor<64x128xf32>" not in head, entry
    assert "tensor<64x128xf32>" in arguments(lows["eval_step"])
    plan = ex.dtype_plan()
    assert plan["paged_decode"]["compute"] == "bf16"
    assert plan["verify"]["compute"] == "bf16"
    assert plan["train_step"]["compute"] == "f32"
    assert {"bf16", "f32"} <= set(plan["paged_decode"]["allowed"])
    f32 = models["gpt2"].executor.dtype_plan()
    assert f32["paged_decode"]["compute"] == "f32"
