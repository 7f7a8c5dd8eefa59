"""Granite 4.0-H's block (Mamba-2 state-space mixers with a state a slot,
grouped-query attention without positions on heads of 64, a dense SwiGLU,
four multipliers, a tied head) at a tiny size, float32, seeded random
weights: the program through its pages AND states against the plain
reference of benchmark/reference/granite4h.py.

Sizes (`Granite4HConfig.tiny`): hidden 64, 4 layers mamba / mamba /
attention / mamba, 8 state-space heads of 16 with a state of 128, 4 query
heads over 2 kv heads of 64.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import granite4h as fam
from benchmark.reference import granite4h as ref
from flexflow_tpu import FFConfig, FFModel, LossType
from flexflow_tpu.ffconst import DataType, OpType
from flexflow_tpu.models.granite4h import build_granite4h
from flexflow_tpu.ops import attrs as A
from flexflow_tpu.ops import mamba2
from flexflow_tpu.ops.pallas import ssd_scan
from flexflow_tpu.ops.registry import LowerCtx
from flexflow_tpu.ops.slot_state import item_chain
from flexflow_tpu.runtime.executor import STATE_OPS, node_key

VOCAB = 96
ROWS = 8        # a packed launch's window (PREFILL_WINDOW_ROWS)


def config():
    """A configuration file's keys, at the tiny size."""
    return {
        "family": "granite4h", "hidden_size": 64, "num_hidden_layers": 4,
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "shared_intermediate_size": 96, "intermediate_size": 96,
        "num_attention_heads": 1, "num_key_value_heads": 1,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 128,
        "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "position_embedding_type": "nope",
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "tie_word_embeddings": True, "vocab_size": VOCAB,
        "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    }


def program_config():
    """The tiny preset keeps heads of 64 over a hidden size of 64, which
    no configuration FILE can say (head_dim is hidden / heads there): the
    family's mapping is tested on its own below."""
    from flexflow_tpu.models.granite4h import Granite4HConfig

    return Granite4HConfig.tiny(VOCAB)


def build(seed=5):
    ff = FFModel(FFConfig(batch_size=1, seed=seed, num_devices=1))
    build_granite4h(ff, program_config(), batch_size=1, seq_len=8,
                    dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def reference_weights(ff):
    cfg = config()
    return fam.reference_weights(ff._params[0], cfg), fam.reference_arch(cfg)


def reference_logits(ff, ids, operand_dtype=None):
    w, arch = reference_weights(ff)
    return ref.logits(w, jnp.asarray(ids), arch=arch,
                      operand_dtype=operand_dtype)


class Launches:
    """The ragged step driven as the server drives it: `slots` states and
    page-table rows; a launch is a list of (slot, first row, tokens), each
    split into 8-row pieces that ride as consecutive items."""

    def __init__(self, ff, slots, max_rows, page_size=8):
        ex = ff.executor
        self.step, (self.tr, self.ntr) = ex.ragged_step_fn(), ff._params
        pages = -(-max_rows // page_size)
        self.caches = ex.init_paged_kv_cache(1 + slots * pages, page_size,
                                             slots=slots)
        self.tables = 1 + np.arange(slots * pages, dtype=np.int32).reshape(
            slots, pages)

    def __call__(self, work, window=ROWS, pads=()):
        """-> [probabilities (rows, V) of each entry of `work`]. `pads`
        are extra (slot, first row) items WITHOUT rows, after the work."""
        items, owner = [], []
        for j, (slot, start, toks) in enumerate(work):
            for off in range(0, len(toks), window):
                items.append((slot, start + off, toks[off:off + window]))
                owner.append(j)
        items += [(s, p, []) for s, p in pads]
        B = len(items)
        ids = np.zeros((B, window), np.int32)
        for i, (_s, _p, t) in enumerate(items):
            ids[i, :len(t)] = t
        slot = np.array([s for s, _p, _t in items], np.int32)
        deps = jnp.broadcast_to(jnp.arange(window, dtype=jnp.int32),
                                (B, window))
        anc = jnp.broadcast_to(
            jnp.tril(jnp.ones((window, window), jnp.bool_)),
            (B, window, window))
        probs, self.caches = self.step(
            self.tr, self.ntr, self.caches, jnp.asarray(self.tables[slot]),
            jnp.asarray(np.array([p for _s, p, _t in items], np.int32)),
            jnp.asarray(np.array([len(t) for _s, _p, t in items], np.int32)),
            deps, anc, jnp.asarray(ids), state_slots=jnp.asarray(slot))
        self.caches.pop("__launch_stats__", None)
        probs = np.asarray(probs, np.float64)
        return [np.concatenate([probs[i, :len(items[i][2])]
                                for i in range(len(owner)) if owner[i] == j])
                for j in range(len(work))]

    def states(self):
        return {nk: {n: np.asarray(b) for n, b in bufs.items()}
                for nk, bufs in self.caches.items() if "s" in bufs}


def served_probs(ff, ids, cuts, slot=1, mate=None):
    """`ids` through pages and states: chunks ending at `cuts`, then a
    token a launch; `mate`, another sequence, rides every launch in slot 0
    in front."""
    run = Launches(ff, 3, len(ids))
    out, start = [], 0
    bounds = list(cuts) + list(range(cuts[-1] + 1, len(ids) + 1))
    for end in bounds:
        work = [(slot, start, ids[start:end])]
        if mate is not None:
            work.insert(0, (0, start, mate[start:end]))
        out.append(run(work, window=ROWS if end - start > 1 else 1)[-1])
        start = end
    return np.concatenate(out)


@pytest.fixture(scope="module")
def tiny():
    return build()


IDS = np.random.default_rng(11).integers(0, VOCAB, 44).astype(np.int32)
MATE = np.random.default_rng(12).integers(0, VOCAB, 44).astype(np.int32)

# float32 on the CPU throughout. Logits are compared in units of their
# row's standard deviation, which the builder's draw sets to about 1
# (models/granite4h.py says why). Program and reference order their sums
# differently (an item's rows solved together against a token at a time;
# probabilities back to logits): 3e-6 is what is measured, the limit is
# five times that. bfloat16 operands miss by 0.036, 2,400 times the limit
# (test_bfloat16_operands_fail_the_models_tolerance), and a bfloat16
# STATE fails the mixer's own test by two orders
# (test_a_bfloat16_state_would_fail): that is what they are tight enough
# for.
TOL_SIGMA = 1.5e-5


def logits_of(probs):
    """Centered log-probabilities: what a softmax keeps of the logits."""
    lp = np.log(probs)
    return lp - lp.mean(-1, keepdims=True)


def centered(logits):
    lg = np.asarray(logits, np.float64)
    return lg - lg.mean(-1, keepdims=True)


def gap_in_sigma(got, want):
    """The largest difference of two (rows, V) arrays of centered logits,
    in standard deviations of `want`'s row."""
    return float((np.abs(got - want).max(-1) / want.std(-1)).max())


# ---------------------------------------------------------------------------
# the op's lowerings


def mixer_case(decay, seed=0, heads=8, p=16, n=128, e=32, rows=37):
    """(attrs, params, x) of one mixer whose log-decays are about `decay`
    a step: dt_bias puts softplus near 1 and A_log = log(-decay)."""
    attrs = A.Mamba2Attrs(e, heads, p, n)
    rng = np.random.default_rng(seed)
    c = attrs.conv_dim

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)

    params = {
        "w_in": u(-0.3, 0.3, e, attrs.inner + c + heads),
        "conv": u(-0.5, 0.5, 4, c), "conv_bias": u(-0.5, 0.5, c),
        "dt_bias": u(0.3, 0.8, heads),
        "a_log": jnp.full((heads,), np.log(-decay), jnp.float32),
        "d_skip": u(0.5, 1.5, heads), "norm": u(0.5, 1.5, attrs.inner),
        "w_out": u(-0.2, 0.2, attrs.inner, e),
    }
    x = jnp.asarray(rng.normal(size=(1, rows, e)), jnp.float32)
    return attrs, params, x


DECAYS = [-0.01, -1.0, -30.0]


@pytest.mark.parametrize("decay", DECAYS)
def test_dense_lowering_equals_the_references_mixer(decay):
    attrs, params, x = mixer_case(decay)
    got = mamba2.dense_mixer(attrs, x, params)[0]
    arch = ref.Arch(attrs.num_heads, attrs.head_dim, attrs.state_dim, 1.0,
                    1.0, 1.0, 1.0, attrs.norm_eps)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(x[0], ref.Mamba(**params), arch)
    # float32 sums in another order; outputs of magnitude ~1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def paged_rows(attrs, params, x, launches, slots=3, interpret=False,
               monkeypatch=None, state_dtype=None):
    """x's rows through `paged_mixer` as `launches` say: each a list of
    (slot, first row of x, rows, x's own row the item starts at or None
    for an item of another request); returns x's rows' outputs."""
    if interpret:
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    specs = attrs.state_specs(slots)
    cache = {k: jnp.zeros(shape, dt or jnp.float32)
             for k, (shape, dt) in specs.items()}
    out = np.zeros(x.shape[1:], np.float32)
    for items in launches:
        W = ROWS if max(n for _s, _p, n, _at in items) > 1 else 1
        xs = np.zeros((len(items), W, x.shape[-1]), np.float32)
        for i, (_s, _p, n, at) in enumerate(items):
            if at is not None:
                xs[i, :n] = x[0, at:at + n]
            else:
                xs[i, :n] = 0.3
        ctx = LowerCtx(
            kv_cache=cache, page_tables=jnp.zeros((len(items), 1), jnp.int32),
            cache_position=jnp.asarray([p for _s, p, _n, _a in items],
                                       jnp.int32),
            ragged_q_lens=jnp.asarray([n for _s, _p, n, _a in items],
                                      jnp.int32),
            state_slots=jnp.asarray([s for s, _p, _n, _a in items],
                                    jnp.int32))
        y, cache = mamba2.paged_mixer(attrs, jnp.asarray(xs), params, ctx)
        if state_dtype is not None:
            cache["s"] = cache["s"].astype(state_dtype).astype(jnp.float32)
        for i, (_s, _p, n, at) in enumerate(items):
            if at is not None:
                out[at:at + n] = np.asarray(y[i, :n])
    return out


# x's 37 rows in slot 1: a chunk of 19 rows (pieces of 8, 8, 3) behind
# another request's piece and an item without rows; a chunk of 13 (8, 5)
# that crosses into the next launch; decode rows beside slot 0's; slot 1
# was USED by another request before (its state and conv rows must not be
# seen: row 0 zeroes them on the device), and slot 2 idles throughout.
RAGGED = (
    [[(1, 0, 8, None), (1, 8, 3, None)]]            # a stranger in slot 1
    + [[(0, 0, 5, None), (0, 5, 0, None), (1, 0, 8, 0), (1, 8, 8, 8),
        (1, 16, 3, 16)]]
    + [[(1, 19, 8, 19), (1, 27, 5, 27), (2, 0, 0, None)]]
    + [[(0, 5 + i, 1, None), (1, 32 + i, 1, 32 + i)] for i in range(5)])


# the same 37 rows where the three forms of an item meet in ONE launch: a
# chunk of 17 rows whose last piece is ONE row (an item of one live row
# directly after pieces of its own slot: the state is where the piece left
# it, nothing is copied in), slot 0's decode row riding behind it and a
# filler; a chunk of 12 (8, 3 and then 1 in the next launch); decode
# launches one row wide with an item without rows between the slots.
TAILS = (
    [[(1, 0, 6, None)]]                             # a stranger in slot 1
    + [[(0, 0, 4, None)]]
    + [[(1, 0, 8, 0), (1, 8, 8, 8), (1, 16, 1, 16), (0, 4, 1, None),
        (0, 5, 0, None)]]
    + [[(0, 5, 1, None), (1, 17, 8, 17), (1, 25, 3, 25), (2, 0, 0, None)]]
    + [[(1, 28, 1, 28), (0, 6, 2, None)]]
    + [[(0, 8 + i, 1, None), (2, 0, 0, None), (1, 29 + i, 1, 29 + i)]
       for i in range(8)])
PLANS = {"ragged": RAGGED, "tails": TAILS}


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("path", ["scan", "kernel"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_paged_lowering_equals_the_dense_one_over_ragged_items(
        decay, path, plan, monkeypatch):
    attrs, params, x = mixer_case(decay, seed=1)
    want = np.asarray(mamba2.dense_mixer(attrs, x, params)[0])
    got = paged_rows(attrs, params, x, PLANS[plan],
                     interpret=path == "kernel", monkeypatch=monkeypatch)
    # float32: an item's rows solved together against a token at a time
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_a_bfloat16_state_would_fail():
    """The control of the tolerances: the same launches with the state
    rounded to bfloat16 between them miss by over a hundred times the
    tolerance."""
    attrs, params, x = mixer_case(-0.01, seed=1)
    want = np.asarray(mamba2.dense_mixer(attrs, x, params)[0])
    got = paged_rows(attrs, params, x, RAGGED, state_dtype=jnp.bfloat16)
    assert np.abs(got - want).max() > 3e-3


# (slot, first row, live rows) an item. "mixed": three slots' runs (one
# fresh, one continued, one of a single decode row), items without rows in
# front, between and behind. "long": an EMPTY item in front of slot 3's run
# (it carries `start`: the state is still copied in), 17 full items, the
# run's partial last piece, a rider of slot 1, a filler.
# "decode": a launch ONE row wide (the one-row kernel): distinct slots, an
# item without rows between them, a request's row 0 (`fresh`), slot 3
# untouched. "riders": the three forms in one launch: 8-row pieces, a
# 3-row tail piece, an item of ONE row directly after pieces of its own
# slot (no `start`: it updates the state where the piece left it), other
# slots' decode rows riding, one of them a request's row 0, and a filler.
LAYOUTS = {
    "mixed": (5, [(4, 0, 0), (4, 0, 8), (4, 8, 3), (2, 7, 1), (2, 0, 0),
                  (0, 40, 8), (1, 0, 0), (1, 0, 0)]),
    "long": (5, [(3, 0, 0)] + [(3, 64 + 8 * i, 8) for i in range(17)]
             + [(3, 200, 5), (1, 77, 1), (1, 0, 0)]),
    "decode": (6, [(0, 12, 1), (1, 0, 0), (2, 0, 1), (4, 33, 1), (4, 0, 0),
                   (5, 7, 1)]),
    "riders": (6, [(3, 40, 8), (3, 48, 8), (3, 56, 1), (1, 16, 8),
                   (1, 24, 3), (0, 9, 1), (2, 0, 1), (5, 70, 1),
                   (5, 0, 0)]),
}


def scan_case(items, n_slots, heads, decay, seed=3, p=64, n=128):
    """A launch of `items` through `ssd_ragged_scan` (interpreted) and
    through the scan over items and rows: ((y, state), (want_y, want_s),
    the state before), dead rows' read-outs zeroed on both sides. The
    launch is one row wide where no item has more."""
    rng = np.random.default_rng(seed)
    B = len(items)
    W = ROWS if max(it[2] for it in items) > 1 else 1
    slots, pos, q_lens = (jnp.asarray([it[k] for it in items], jnp.int32)
                          for k in range(3))
    alive = (np.arange(W)[None, :] < np.asarray(q_lens)[:, None])[..., None]
    xh = jnp.asarray(rng.normal(size=(B, W, heads, p)), jnp.float32)
    b_in = jnp.asarray(rng.normal(size=(B, W, n)), jnp.float32)
    c_out = jnp.asarray(rng.normal(size=(B, W, n)), jnp.float32)
    dt = jnp.asarray(np.where(alive, rng.uniform(0.01, 1.0, (B, W, heads)),
                              0.0), jnp.float32)
    a = jnp.asarray(np.where(alive, decay * rng.uniform(0.5, 1.5,
                                                        (B, W, heads)), 0.0),
                    jnp.float32)
    state = jnp.asarray(rng.normal(size=(n_slots, heads, p, n)), jnp.float32)
    chain = item_chain(slots, pos, q_lens)
    want_y, want_s = mamba2.scan_items(xh, b_in, c_out, dt, a, chain, state)
    slot, start, fresh, _last = chain
    y, s = ssd_scan.ssd_ragged_scan(
        (dt[..., None] * xh).reshape(B, W, heads * p), b_in, c_out, a, state,
        slot, start.astype(jnp.int32), fresh.astype(jnp.int32), q_lens,
        heads=heads, interpret=True)
    y = np.where(alive[..., None], np.asarray(y).reshape(B, W, heads, p), 0.0)
    want_y = np.where(alive[..., None], np.asarray(want_y), 0.0)
    return ((y, np.asarray(s)), (want_y, np.asarray(want_s)),
            np.asarray(state))


def assert_scan_equals_oracle(got, want, before, items):
    (y, s), (want_y, want_s) = got, want
    # float32 at full precision; read-outs and states of magnitude ~10-100
    scale_y, scale_s = np.abs(want_y).max(), np.abs(want_s).max()
    assert np.abs(y - want_y).max() <= 2e-6 * scale_y
    assert np.abs(s - want_s).max() <= 2e-6 * scale_s
    untouched = sorted(set(range(len(before))) - {it[0] for it in items})
    assert untouched
    np.testing.assert_array_equal(s[untouched], before[untouched])


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_interpreted_equals_its_oracle(decay, layout):
    """`ssd_ragged_scan` (interpreted) against the scan over items and
    rows, at the published head shape (P 64, N 128), 16 heads."""
    n_slots, items = LAYOUTS[layout]
    got, want, before = scan_case(items, n_slots, 16, decay)
    assert_scan_equals_oracle(got, want, before, items)


# heads, launch width -> heads a grid step takes at P 64, N 128: a launch
# one row wide takes every head its budget holds, one that may hold a
# solve a lane tile of `small`
HEADS_A_STEP = {(8, 1): 8, (16, 1): 16, (64, 1): 64,
                (8, ROWS): 8, (16, ROWS): 8, (64, ROWS): 8}


@pytest.mark.parametrize("heads,width", sorted(HEADS_A_STEP))
def test_every_heads_a_step_against_the_oracle(heads, width):
    """Each number of heads a step the derivation returns for 8, 16 and
    64 heads, in both kernels, against the scan over items and rows."""
    assert (ssd_scan._heads_a_step(heads, 64, 128, width)
            == HEADS_A_STEP[heads, width])
    items = [(2, 5, 1), (0, 0, 1), (0, 0, 0)]
    if width > 1:
        items = [(1, 16, 8), (1, 24, 2), (1, 26, 1)] + items
    got, want, before = scan_case(items, 4, heads, -1.0, seed=heads)
    assert_scan_equals_oracle(got, want, before, items)


def test_heads_a_step_follow_the_budget():
    """What the states of a step may take of VMEM decides, in whole lane
    tiles of `small` that divide the heads; never under one tile."""
    per_head = 4 * 64 * 128 * 4         # in and out, double-buffered
    assert ssd_scan.STATE_VMEM // per_head == 64
    assert ssd_scan._heads_a_step(128, 64, 128, 1) == 64
    assert ssd_scan._heads_a_step(64, 64, 512, 1) == 16
    assert ssd_scan._heads_a_step(48, 64, 256, 1) == 24
    assert ssd_scan._heads_a_step(24, 64, 128, 1) == 24
    assert ssd_scan._heads_a_step(64, 64, 8192, 1) == 8
    assert ssd_scan._heads_a_step(64, 64, 128, 2) == 8


# ---------------------------------------------------------------------------
# attention: a score scale that is not kdim ** -0.5, heads of 64


def attention_case(scale, heads=4, kv=2, d=64, e=48, rows=21, seed=2):
    attrs = A.MultiHeadAttentionAttrs(e, heads, kv, d, True,
                                      softmax_scale=scale)
    rng = np.random.default_rng(seed)

    def u(*shape):
        return jnp.asarray(rng.uniform(-0.4, 0.4, shape), jnp.float32)

    params = {"wq": u(e, heads, d), "wk": u(e, kv, d), "wv": u(e, kv, d),
              "wo": u(heads, d, e)}
    x = jnp.asarray(rng.normal(size=(1, rows, e)), jnp.float32)
    arch = ref.Arch(1, 1, 1, 1.0, 1.0,
                    d ** -0.5 if scale is None else scale, 1.0, 1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x[0], ref.Attention(**params), arch,
                              ref.lower_precision(None))
    return attrs, params, x, np.asarray(want)


@pytest.mark.parametrize("scale", [None, 0.015625, 0.4])
def test_softmax_scale_through_the_dense_lowering(scale):
    from flexflow_tpu.ops.registry import get_lowering

    attrs, params, x, want = attention_case(scale)
    got = get_lowering(OpType.MULTIHEAD_ATTENTION)(
        attrs, [x, x, x], params, LowerCtx())[0][0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("scale", [None, 0.015625, 0.4])
@pytest.mark.parametrize("path", ["gather", "ragged"])
def test_softmax_scale_and_heads_of_64_through_the_paged_paths(
        scale, path, monkeypatch):
    """A chunk of 16 rows as two pieces, then five decode rows, through
    the page pool: the gather path, and the ragged kernel interpreted,
    which takes the two kv heads of 64 as ONE head of 128."""
    from flexflow_tpu.ops.registry import get_lowering
    from flexflow_tpu.paged import attention as pa

    if path == "ragged":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    attrs, params, x, want = attention_case(scale)
    page, pages = 8, 4
    cache = {"k": jnp.zeros((1 + pages, page, 2 * 64), jnp.float32),
             "v": jnp.zeros((1 + pages, page, 2 * 64), jnp.float32)}
    table = jnp.arange(1, 1 + pages, dtype=jnp.int32)[None]
    lower = get_lowering(OpType.MULTIHEAD_ATTENTION)
    out = np.zeros(x.shape[1:], np.float32)
    tril = jnp.tril(jnp.ones((ROWS, ROWS), jnp.bool_))
    for items in ([(0, 8), (8, 8)], *[[(16 + i, 1)] for i in range(5)]):
        W = max(n for _p, n in items)
        xs = jnp.stack([jnp.pad(x[0, p:p + n], ((0, W - n), (0, 0)))
                        for p, n in items])
        B = len(items)
        ctx = LowerCtx(
            kv_cache=cache, page_tables=jnp.repeat(table, B, 0),
            cache_position=jnp.asarray([p for p, _n in items], jnp.int32),
            ragged_q_lens=jnp.asarray([n for _p, n in items], jnp.int32),
            ragged_depths=jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32),
                                           (B, W)),
            ragged_anc=jnp.broadcast_to(tril[:W, :W], (B, W, W)))
        y = lower(attrs, [xs, xs, xs], params, ctx)[0]
        cache = dict(ctx.cache_updates)
        for i, (p, n) in enumerate(items):
            out[p:p + n] = np.asarray(y[i, :n])
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)
    assert pa.paged_attention_available(64, page, interpret=False,
                                        kv_heads=3) is False


# ---------------------------------------------------------------------------
# the whole model


def test_the_dense_forward_equals_the_reference(tiny):
    probs = np.asarray(tiny.predict(IDS[None, :8])[0], np.float64)
    want = centered(reference_logits(tiny, IDS[:8]))
    assert gap_in_sigma(logits_of(probs), want) < TOL_SIGMA


@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_prefill_and_decode_through_pages_and_states_equal_the_reference(
        tiny, path, monkeypatch):
    """Chunks of 19 and 13 rows (pieces of 8, 8, 3 and 8, 5) to position
    32, then token by token to 44, another sequence beside it in every
    launch, against the reference's one full forward: logits compared."""
    ff = tiny
    if path == "kernel":
        monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
        ff = build()        # its step functions trace under the flag
    got = served_probs(ff, IDS, (19, 32), mate=MATE)
    assert np.isfinite(got).all() and (got > 0).all()
    assert gap_in_sigma(logits_of(got),
                        centered(reference_logits(ff, IDS))) < TOL_SIGMA


def test_bfloat16_operands_fail_the_models_tolerance(tiny):
    """The control: the reference with every product's operands rounded
    to bfloat16 misses the float32 reference by a hundred times the
    limit, so a program that computed in a lower precision than stated
    would fail."""
    exact = centered(reference_logits(tiny, IDS))
    low = centered(reference_logits(tiny, IDS, jnp.bfloat16))
    assert gap_in_sigma(low, exact) > 100 * TOL_SIGMA


def test_through_the_server_at_three_slots(tiny):
    """`serve_generation(paged=True)` at 3 slots, a pool too small for
    all, so one is evicted, its state dropped and recomputed: every
    request's greedy tokens are the reference's argmax, the invariant
    catalog holds before every launch, and the metrics name the op."""
    ff = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (44, 46, 9, 21, 30)]
    server = ff.serve_generation(
        paged=True, slots=3, max_len=96, page_size=8, num_pages=20,
        prefill_chunk=16, prefix_cache=False)
    launch = server._launch

    def checked(*a, **kw):
        server._check_invariants()
        return launch(*a, **kw)

    server._launch = checked
    try:
        futs = [server.submit(p, 20) for p in prompts]
        toks = [np.asarray(f.result()) for f in futs]
    finally:
        server.stop()
    m = server.metrics()
    for p, t in zip(prompts, toks):
        seq = np.concatenate([p, t])
        want = np.asarray(reference_logits(ff, seq))
        np.testing.assert_array_equal(
            want[len(p) - 1:len(seq) - 1].argmax(-1), t)
    state = m["state"]
    assert state["kinds"] == ["ssd"] and state["layers"] == 3
    assert state["bytes_per_slot"] == 3 * (8 * 16 * 128 * 4
                                           + 3 * (128 + 256) * 4)
    assert state["resets"] == len(prompts) + m["preemptions"]
    assert m["kv_bytes_per_token"] == 2 * 2 * 64 * 4    # one layer, K and V


def test_launch_spans_name_the_op_that_is_there(tiny):
    from flexflow_tpu import obs
    from flexflow_tpu.runtime.executor import launch_columns

    handed = []         # q_lens as each launch's step program is handed them
    rec = obs.enable()
    try:
        srv = tiny.serve_generation(paged=True, slots=2, max_len=64,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=False)
        step = srv._step

        def rec_step(tr, ntr, caches, tbl, pos, qls, deps, anc, **fed):
            packed = np.asarray(fed["packed"])
            at, _ = launch_columns(anc.shape[1], width=packed.shape[1])
            handed.append(packed[:, at["q_lens"]])
            return step(tr, ntr, caches, tbl, pos, qls, deps, anc, **fed)

        srv._step = rec_step
        try:
            futs = [srv.submit(IDS[:37], 3), srv.submit(IDS[:9], 4)]
            for f in futs:
                f.result()
        finally:
            srv.stop()
    finally:
        obs.disable()
    spans = [ev[4] for ev in rec.events if ev[0] == "launch_dispatch"]
    first = spans[0]
    assert (first["state_slots"], first["slots"], first["ssd_rows"],
            first["ssd_pieces"], first["ssd_one_row"]) == (1, 2, 16, 2, 0)
    assert "kda_rows" not in first and "kda_one_row" not in first
    assert first["state_bytes_per_slot"] == 3 * (8 * 16 * 128 * 4
                                                 + 3 * 384 * 4)
    assert first["kv_bytes_per_token"] == 2 * 2 * 64 * 4
    # the host's count of items of ONE live row is what the kernel's
    # `rows == 1` decides on the device, launch by launch: a chunk's
    # one-row tail (9 = 8 + 1), decode rows riding and alone
    assert len(spans) == len(handed)
    assert ([sp["ssd_one_row"] for sp in spans]
            == [int((q == 1).sum()) for q in handed])
    assert ([sp["ssd_pieces"] for sp in spans]
            == [int((q > 0).sum()) for q in handed])
    one_row = sum(sp["ssd_one_row"] for sp in spans)
    assert one_row >= 6
    state = srv.metrics()["state"]
    assert state["items_one_row"] == one_row
    assert state["items"] == sum(sp["ssd_pieces"] for sp in spans)


def test_the_tied_head_is_one_leaf(tiny):
    ex = tiny.executor
    assert OpType.MAMBA2 in STATE_OPS
    names = {node_key(n).rsplit("_", 1)[0]: n for n in ex.topo}
    assert names["lm_head"].op_type == OpType.TIED_HEAD
    trainable = tiny._params[0]
    leaves = {k.rsplit("_", 1)[0]: v for k, v in trainable.items()}
    assert "lm_head" not in leaves
    assert leaves["tok_emb"]["kernel"].shape == (VOCAB, 64)
    total = sum(x.size for x in jax.tree.leaves(trainable))
    mamba = 64 * (128 + 384 + 8) + 4 * 384 + 384 + 3 * 8 + 128 + 128 * 64
    attn = 2 * 64 * 256 + 2 * 64 * 128
    assert total == (VOCAB * 64 + 3 * mamba + attn + 4 * 3 * 64 * 96
                     + 9 * 64)


def test_slot_state_invariant_covers_both_ops_leaves(tiny):
    """The server's state leaves against what their op declares: as they
    are, nothing; a leaf of another shape or dtype is named."""
    from flexflow_tpu.analysis import pool_invariants as inv

    srv = tiny.serve_generation(paged=True, slots=2, max_len=32,
                                page_size=8, prefix_cache=False)
    try:
        leaves = srv._state_leaves()
    finally:
        srv.stop()
    assert len(leaves) == 6 and {held for _n, held, _d in leaves} == {
        ((2, 8, 16, 128), "float32"), ((2, 3, 384), "float32")}
    ok = [(None, 0), (None, 0)]
    assert inv.check_slot_state(ok, {}, [], leaves) == []
    name, held, declared = leaves[0]
    v = inv.check_slot_state(ok, {}, [], [(name, held, (
        (2, 8, 16, 64), "float32"))])
    assert len(v) == 1 and name in v[0] and "declares" in v[0]
    assert "whichever state op" in inv.by_name("slot-state").description


@pytest.mark.parametrize("option", [
    dict(paged=False), dict(prefix_cache=True), dict(kv_dtype="int8"),
    dict(host_tier=4), dict(search_budget=2)])
def test_unsupported_serving_options_are_refused_by_name(tiny, option):
    kw = dict(paged=True, slots=2, max_len=32, page_size=8,
              prefix_cache=False)
    kw.update(option)
    with pytest.raises(ValueError, match="mamba2"):
        tiny.serve_generation(**kw)


def test_the_family_maps_a_files_keys_and_refuses_what_is_not_built():
    cfg = config()
    prog = fam.program_config(dict(cfg, num_attention_heads=1))
    assert (prog.mamba_heads, prog.mamba_head_dim, prog.mamba_state,
            prog.head_dim) == (8, 16, 128, 64)
    assert prog.attention_multiplier == 0.015625 and prog.logits_scaling == 8
    for key, bad in (("num_local_experts", 4), ("mamba_n_groups", 2),
                     ("position_embedding_type", "rope"),
                     ("tie_word_embeddings", False),
                     ("mamba_conv_bias", False)):
        with pytest.raises(ValueError):
            fam.check(dict(cfg, **{key: bad}))
    with pytest.raises(ValueError, match="lacks"):
        fam.check({k: v for k, v in cfg.items() if k != "logits_scaling"})


# ---------------------------------------------------------------------------
# what the step's operations are FOR (tests/serving_scope_checks.py; the
# other five graph kinds run the same checks in tests/test_launch_packed.py)


@pytest.mark.parametrize("launch", ["decode", "chunk"])
def test_every_heavy_instruction_of_the_step_has_a_group(tiny, launch):
    import serving_scope_checks as scope_checks

    seen = scope_checks.check_every_heavy_instruction_has_a_group(
        tiny, launch)
    assert {g for g, _n in seen if g} == {"attn", "state", "ffn", "head",
                                           "glue"}
    # the group is the node's OpType's, not its key's spelling: both kinds
    # of mixer are named `l<i>_mixer`
    mixers = {g for g, n in seen if n and "_mixer_" in n and "norm" not in n}
    assert mixers == {"attn", "state"}


@pytest.mark.parametrize("launch", ["decode", "chunk"])
def test_the_steps_scopes_change_nothing_but_names(tiny, launch,
                                                   monkeypatch):
    import serving_scope_checks as scope_checks

    scope_checks.check_scopes_change_nothing_but_names(tiny, launch,
                                                       monkeypatch)
