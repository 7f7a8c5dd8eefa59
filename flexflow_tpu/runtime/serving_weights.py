"""The weight tree a server launches with.

A model keeps a float32 master of every weight it declares narrower
(Executor.init_params: the optimizer updates the master), and each
serving program converts such a leaf to its declared dtype where it
uses it. A server does not train, so the convert yields the same
numbers on every launch while the matmul behind it streams twice the
bytes the declared width needs. `serving_params` stores those leaves at
the declared dtype ONCE; the programs are then traced against the
narrow leaves, the `astype` at the use site is an identity, and a
launch reads the weights at the width they are declared.

Which leaves: those whose stored float dtype is WIDER than the declared
one (`WeightSpec.shape.dtype`) and whose EVERY use in the serving steps
is a convert to exactly that dtype, read off the steps' jaxprs
(`served_dtypes`). A leaf a step reads wider (a norm's scale, in
float32) stays as stored, so its values need not be representable
narrower; a leaf stored as declared, or narrower, is the model's own
array. Nothing here looks at a leaf's, a node's or a model's name.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

# primitives that move elements and compute nothing, taking the array
# as operand 0: convert(move(x)) is move(convert(x)) bit for bit
_MOVES = frozenset({
    "reshape", "transpose", "squeeze", "expand_dims", "broadcast_in_dim",
    "slice", "dynamic_slice", "gather", "copy", "copy_p",
})
# calls whose body takes the equation's operands and returns its
# results, one for one: primitive name -> the param holding the body
_CALLS = {
    "jit": "jaxpr", "pjit": "jaxpr", "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr", "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr", "remat2": "jaxpr",
    "checkpoint": "jaxpr",
}

def _follow(jaxpr, origin: Dict[Any, int], want, converted: set,
            bad: set) -> None:
    """Walk `jaxpr` in order. `origin` maps a variable that still holds
    leaf i's stored elements (moved at most) to i. A convert of such a
    variable to want[i] puts i in `converted`; any other consumer that
    is neither a move nor a call walked the same way puts i in `bad`.
    What reaches `jaxpr.outvars` unconverted is still in `origin`, for
    the caller."""
    for eqn in jaxpr.eqns:
        hits = {p: origin[v] for p, v in enumerate(eqn.invars)
                if not isinstance(v, Literal) and v in origin}
        if not hits:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            (leaf,) = hits.values()
            if eqn.params["new_dtype"] == want[leaf]:
                converted.add(leaf)
            else:
                bad.add(leaf)
        elif name in _MOVES and list(hits) == [0]:
            origin[eqn.outvars[0]] = hits[0]
        elif name in _CALLS or name == "scan":
            body = eqn.params[_CALLS.get(name, "jaxpr")]
            body = getattr(body, "jaxpr", body)     # ClosedJaxpr or Jaxpr
            if len(body.invars) != len(eqn.invars):
                bad.update(hits.values())
                continue
            if name == "scan":
                # consts and per-iteration slices are read-only inside;
                # a carry is rewritten every iteration
                lo = eqn.params["num_consts"]
                carry = range(lo, lo + eqn.params["num_carry"])
                bad.update(i for p, i in hits.items() if p in carry)
            inner = {body.invars[p]: i for p, i in hits.items()}
            _follow(body, inner, want, converted, bad)
            for out, v in zip(eqn.outvars, body.outvars):
                if not isinstance(v, Literal) and v in inner:
                    if name == "scan":
                        bad.add(inner[v])
                    else:
                        origin[out] = inner[v]
        else:
            bad.update(hits.values())


def _narrower_float(declared, stored):
    """`declared` as a dtype where both are float dtypes and a leaf
    stored at `stored` is wider than declared; else None."""
    declared, stored = jnp.dtype(declared), jnp.dtype(stored)
    if (jnp.issubdtype(declared, jnp.floating)
            and jnp.issubdtype(stored, jnp.floating)
            and declared.itemsize < stored.itemsize):
        return declared
    return None


def _qualifying(jaxpr, want) -> set:
    """The leaves of `want` ({index among jaxpr.invars: declared dtype})
    that `jaxpr` converts to their declared dtype and does nothing else
    with; handing one back unconverted is another use."""
    converted, bad = set(), set()
    origin = {jaxpr.invars[i]: i for i in want}
    _follow(jaxpr, origin, want, converted, bad)
    bad.update(origin[v] for v in jaxpr.outvars
               if not isinstance(v, Literal) and v in origin)
    return converted - bad


def _step_jaxprs(executor, params):
    """The jaxpr of every step a server of this graph launches, traced
    against abstract copies of `params` at a one-row shape: the paged
    ragged step (which a speculative server verifies with, too) and the
    dense cached step. Each one's leading invars are the leaves of
    `params`, in tree order."""
    from flexflow_tpu.ffconst import OpType

    if len(executor.input_nodes) != 1:
        return
    tr, ntr = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if executor.can_paged_decode():
        yield executor.ragged_step_fn().trace(
            tr, ntr, executor.paged_kv_cache_specs(2, 16, slots=1),
            *executor.ragged_step_avals(1, 1, 1)).jaxpr.jaxpr
    if any(n.op_type in (OpType.MULTIHEAD_ATTENTION, OpType.RING_ATTENTION,
                         OpType.PIPELINE) for n in executor.topo):
        ids = executor.ragged_step_avals(1, 1, 1)[-1]
        yield executor.decode_fn().trace(
            tr, ntr, jax.eval_shape(lambda: executor.init_kv_cache(1, 8)),
            jax.ShapeDtypeStruct((), jnp.int32), ids).jaxpr.jaxpr


def served_dtypes(executor, params) -> Dict[Tuple, Any]:
    """{tree path of a leaf: dtype to store it at} for the leaves of
    `params` = (trainable, nontrainable) — arrays or ShapeDtypeStructs —
    a server should hold narrower than they are stored: a float leaf
    declared narrower than stored that EVERY serving step of this graph
    converts to the declared dtype, and does nothing else with. Empty
    where nothing is declared narrower than it is stored (a float32
    model, a model stored at `FFConfig.weight_dtype`), and where the
    graph has no serving step to trace."""
    specs = executor.weight_specs()
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(params)[0])
    narrower = map(_narrower_float,
                   (specs[p[1].key][p[2].key].shape.dtype.jnp_dtype
                    for p in paths), (leaf.dtype for leaf in leaves))
    want = {i: d for i, d in enumerate(narrower) if d is not None}
    if not want:
        return {}
    memo = executor.served_dtypes_memo
    sig = tuple(jnp.dtype(x.dtype).name for x in leaves)
    if sig not in memo:
        steps = list(_step_jaxprs(executor, params))
        keep = set(want) if steps else set()
        for jaxpr in steps:
            keep &= _qualifying(jaxpr, want)
        memo[sig] = {paths[i]: want[i] for i in sorted(keep)}
    return memo[sig]


def _narrow(leaf, dtype):
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(leaf.shape, dtype,
                                    sharding=leaf.sharding)
    return leaf.astype(dtype)     # on the device, in the leaf's sharding


def serving_params(executor, params):
    """(trainable, nontrainable) as a server launches with them: the
    leaves `served_dtypes` names converted once, every other leaf the
    SAME object as in `params` — and `params` itself where nothing is
    converted. Takes arrays (a server) or ShapeDtypeStructs
    (lowered_modules, which must lower what a server launches)."""
    want = served_dtypes(executor, params)
    if not want:
        return params
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (_narrow(leaf, want[path]) if path in want
                            else leaf), tuple(params))


def weight_stats(masters, served) -> Dict[str, int]:
    """What `server.metrics()["weights"]` reports: the bytes of the
    model's tree, of the tree the launches are handed, and how many
    leaves differ in dtype between them."""
    m, s = jax.tree.leaves(masters), jax.tree.leaves(served)
    return {
        "bytes_master": int(sum(x.nbytes for x in m)),
        "bytes_served": int(sum(x.nbytes for x in s)),
        "leaves_cast": sum(a.dtype != b.dtype for a, b in zip(m, s)),
    }
