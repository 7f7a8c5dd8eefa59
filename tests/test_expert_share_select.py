"""The expert router chooses without sorting (ops/expert_share.py `_top_k`,
`_select`, `route`): k rounds of (maximum, first index that holds it,
strike it out) give `jax.lax.top_k`'s ids, order and values to the bit,
ties included, at the three routers of the benchmark's expert cells. The
oracle is the form the tree had through PR 45, every choice a `lax.top_k`
(`tools/chip_kernels.py` `select_by_sort`: one copy, which the chip tool
also times the rounds against)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flexflow_tpu.ops import expert_share
from flexflow_tpu.ops.attrs import ExpertShareAttrs
from tools.chip_kernels import ROUTERS, route_cases, select_by_sort

ROWS = 24


def attrs_of(name, **changed):
    return ExpertShareAttrs(hidden_dim=32, **{**ROUTERS[name][1], **changed})


def route_by_sort(attrs, x, router, bias=None):
    """`route` as it was through PR 45."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = (jax.nn.sigmoid(logits) if attrs.score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    w, ids = select_by_sort(attrs, probs, bias)
    if attrs.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), w * attrs.routed_scale


def same_bits(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


# --- score sets built to tie -------------------------------------------------
# each: (RandomState, E, k, group size) -> (ROWS, E) float32 in (0, 1), every
# value a multiple of 1/1024 so that sums and biased scores stay exact

def _distinct(rs, E):
    return np.stack([rs.permutation(E) for _ in range(ROWS)]
                    ).astype(np.float32) / 1024 + 1 / 1024


def ties_inside_a_group(rs, E, k, size):
    s = _distinct(rs, E)
    s[:, 1] = s[:, 3] = s[:, size - 1] = 0.75      # three times the largest
    s[:, 2] = s[:, 5] = 0.625
    return s


def ties_across_groups(rs, E, k, size):
    s = _distinct(rs, E)
    s[:, [0, size, E - 1]] = 0.875                  # first, second, last group
    s[:, [size - 1, E - size]] = 0.8125
    return s


def ties_at_the_kth_place(rs, E, k, size):
    """k - 1 clear winners, then five equal claimants of the last place."""
    s = _distinct(rs, E) / 4
    cols = rs.permutation(E)
    s[:, cols[:k - 1]] = 0.5 + np.arange(k - 1, dtype=np.float32) / 64
    s[:, cols[k - 1:k + 4]] = 0.4375
    return s


def all_equal(rs, E, k, size):
    return np.full((ROWS, E), 0.5, np.float32)


def quantised(rs, E, k, size):
    return np.round(rs.uniform(0, 1, (ROWS, E)) * 64).astype(
        np.float32) / 64


def equal_groups_at_the_boundary(rs, E, k, size):
    """Every group's two largest sum to the same: which groups open is the
    tie rule's alone; then two groups a notch above, the rest still tied."""
    s = _distinct(rs, E) / 8
    s[:, 0::size] = 0.5
    s[:, 1::size] = 0.25
    s[ROWS // 2:, 2 * size] = 0.625
    s[ROWS // 2:, 5 * size + 1] = 0.375
    return s


TIES = [ties_inside_a_group, ties_across_groups, ties_at_the_kth_place,
        all_equal, quantised]


def bias_that_equalises(scores, rs):
    """A bias that lifts lower scores onto higher ones: biased scores tie
    where the unbiased differ (multiples of 1/1024 stay exact)."""
    E = scores.shape[1]
    bias = np.zeros((E,), np.float32)
    lifted = rs.permutation(E)[:E // 4]
    bias[lifted] = rs.randint(1, 64, lifted.size).astype(np.float32) / 1024
    return bias


# --- the routine against lax.top_k -------------------------------------------

@pytest.mark.parametrize("build", TIES, ids=lambda f: f.__name__)
@pytest.mark.parametrize("E,k", [(512, 8), (128, 4), (64, 8), (64, 2),
                                 (8, 4)])
def test_the_rounds_are_lax_top_k(E, k, build):
    """(512, 8), (128, 4), (64, 8): the three routers' last choice; (64, 2)
    a group's two largest, (8, 4) the open groups of eight."""
    x = jnp.asarray(build(np.random.RandomState(E + k), E, k, max(E // 8, 2)))
    same_bits(expert_share._top_k(x, k), lax.top_k(x, k))


def test_the_rounds_over_a_view_of_groups_and_with_closed_groups():
    """The shapes `_select` hands the routine: (T, groups, size), and a
    row whose closed groups read -inf."""
    rs = np.random.RandomState(1)
    x = jnp.asarray(quantised(rs, 512, 8, 64))
    same_bits(expert_share._top_k(x.reshape(ROWS, 8, 64), 2),
              lax.top_k(x.reshape(ROWS, 8, 64), 2))
    closed = jnp.where((jnp.arange(512) // 64) % 2 == 0, -jnp.inf, x)
    same_bits(expert_share._top_k(closed, 8), lax.top_k(closed, 8))
    with pytest.raises(ValueError, match="largest of 4"):
        expert_share._top_k(x[:, :4], 5)


# --- the selection and the whole of route, before / after --------------------

@pytest.mark.parametrize("build", TIES + [equal_groups_at_the_boundary],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("with_bias", ["zero_bias", "equalising_bias"])
def test_ling3_selection_is_the_sorting_forms(build, with_bias):
    attrs = attrs_of("ling512")
    rs = np.random.RandomState(7)
    scores = build(rs, 512, 8, 64)
    bias = (np.zeros((512,), np.float32) if with_bias == "zero_bias"
            else bias_that_equalises(scores, rs))
    got = expert_share._select(attrs, jnp.asarray(scores), jnp.asarray(bias))
    same_bits(got, select_by_sort(attrs, jnp.asarray(scores),
                                  jnp.asarray(bias)))
    ids = np.asarray(got[1])
    assert all(len(set(r)) == 8 and len(set(g // 64 for g in r)) <= 4
               for r in ids.tolist())


@pytest.mark.parametrize("name", ["small4_128", "mellum2_64"])
def test_a_plain_routers_program_is_what_it_was(name):
    """No bias, one group: `route` keeps its one short sort (PERF.md
    section 6, PR 46), so the two softmax routers' cells run the programs
    they ran, to the byte of the StableHLO."""
    attrs = attrs_of(name)
    avals = (jax.ShapeDtypeStruct((ROWS, 32), jnp.bfloat16),
             jax.ShapeDtypeStruct((32, attrs.n_experts), jnp.bfloat16))
    got, want = (jax.jit(lambda x, r, f=f: f(attrs, x, r)).lower(
        *avals).as_text() for f in (expert_share.route, route_by_sort))
    assert got == want and "top_k" in got


def test_a_bias_without_groups_and_groups_without_a_bias():
    rs = np.random.RandomState(13)
    scores = quantised(rs, 64, 8, 8)
    bias = jnp.asarray(bias_that_equalises(scores, rs))
    scores = jnp.asarray(scores)
    for attrs, b in ((attrs_of("mellum2_64", select_bias=True), bias),
                     (attrs_of("mellum2_64", n_group=4, topk_group=2), None)):
        same_bits(expert_share._select(attrs, scores, b),
                  select_by_sort(attrs, scores, b))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_route_is_bit_for_bit_what_it_was(name, seed):
    """Random logits quantised to 1/64 through an identity router, so ties
    are common in the scores whatever the scoring function; weights
    normalised and scaled as the cells' attrs say."""
    attrs = attrs_of(name)
    E = attrs.n_experts
    rs = np.random.RandomState(seed)
    x = jnp.asarray(np.round(rs.randn(ROWS, E) * 64) / 64, jnp.float32)
    bias = (jnp.asarray(rs.randint(-3, 4, E) / 1024, jnp.float32)
            if attrs.select_bias else None)
    got, want = (jax.jit(lambda x, r, b, f=f: f(attrs, x, r, b))(
        x, jnp.eye(E, dtype=jnp.float32), bias)
        for f in (expert_share.route, route_by_sort))
    same_bits(got, want)
    probs = np.asarray(jax.nn.sigmoid(x) if attrs.score == "sigmoid"
                       else jax.nn.softmax(x, axis=-1))
    assert any(len(set(r)) < E for r in probs.tolist())     # ties happened


# --- no sort can come back unseen --------------------------------------------

def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_the_grouped_routers_program_holds_no_sort():
    """Read in the jaxpr, so on any backend: a later edit cannot bring a
    sort (or the gather that cost as much as the rounds) back unseen."""
    attrs = attrs_of("ling512")
    closed = jax.make_jaxpr(
        lambda x, r, b: expert_share.route(attrs, x, r, b))(
            jnp.zeros((ROWS, 16)), jnp.zeros((16, 512)), jnp.zeros((512,)))
    found = set(_primitives(closed.jaxpr))
    assert "reduce_max" in found                    # the walk sees inside
    assert not found & {"top_k", "sort", "approx_top_k", "gather"}, found


@pytest.mark.parametrize("changed", [
    dict(topk_group=1),                 # 64 open outputs hold 8: allowed
    dict(n_group=128, topk_group=1),    # 4 open outputs, 8 asked
    dict(n_group=128, topk_group=2, k=16),
])
def test_open_groups_must_hold_k_outputs(changed):
    attrs = attrs_of("ling512", **changed)
    scores = jnp.asarray(quantised(np.random.RandomState(3), 512, 8, 64))
    bias = jnp.zeros((512,), jnp.float32)
    open_outputs = attrs.topk_group * (512 // attrs.n_group)
    if attrs.k <= open_outputs:
        same_bits(expert_share._select(attrs, scores, bias),
                  select_by_sort(attrs, scores, bias))
        return
    with pytest.raises(ValueError, match=f"of the {open_outputs} outputs"):
        expert_share.route(attrs, jnp.zeros((ROWS, 16)),
                           jnp.zeros((16, 512)), bias)


# --- the chip tool's cases, here ---------------------------------------------

@pytest.mark.parametrize("name", sorted(route_cases()))
def test_the_chip_tools_route_cases_agree_to_the_bit(name):
    fn, args, ref = route_cases()[name]()
    rows, fields = ROUTERS[name[len("route_"):]]
    assert args[0].shape == (rows, fields["n_experts"])
    same_bits(jax.jit(fn)(*args), jax.jit(ref)(*args))
