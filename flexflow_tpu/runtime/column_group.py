"""A remat="hidden" SwiGLU diamond whose views split the hidden dimension
by column over ONE mesh axis, run per shard under `shard_map`, so that its
input gradient crosses that axis once.

Why: the executor constrains only a node's outputs, so under plain GSPMD
the backward pass's reductions sit wherever XLA's partitioner puts them:
after each dot whose contracted dimension is split, on the dot's float32
result. `gate` and `up` share their input, their two transposed dots each
get an all-reduce of their own, and JAX's `add_any` joins the results
after: `allreduce(a) + allreduce(b)` where `allreduce(a + b)` is the same
number (on the v5e the TPU compiler does not merge them while they read
float32 partial sums, PERF.md section 6, PRs 38 and 39; it would if they
read bfloat16, PR 42, which costs every leaf precision).

How: the map's body is handed one copy of the shared input a shard, a
broadcast along a new leading dimension that is split over the axis. Going
forward that costs nothing (each chip holds the input whole and keeps its
own copy). Going back, each shard's body adds its two partial gradients
on the chip, and the broadcast's transpose is ONE sum over the leading
dimension, which the partitioner lowers to one all-reduce of the local
sums at the activations' dtype. The merge is a property of the program JAX
hands XLA, not a pass that may or may not run.

The map is manual over the WHOLE mesh and the weights enter the same way,
a copy a shard of the axes the batch dimension is split over, so that the
sum of a kernel's gradient over those axes is the broadcast's transpose
too, placed by the partitioner where it placed the gradient sync before,
and the body holds no collective. (A map over the one axis alone, the
batch's left to GSPMD, compiles too, but jax 0.9.0 then constrains every
value inside it, and on the v5e the compiler answered by flipping the
layout of both kernels and their Adam moments on the way in and out: 72
copies, 15.8 ms a step; PERF.md section 6, PR 39.)

What it rounds: each shard's local sum of the two partial input gradients
leaves the body at the activations' dtype, so under bfloat16 it is rounded
BEFORE it crosses, where the fallback's two all-reduces read the dots'
float32 partial sums and round after (half the bytes on the link; every
gradient leaf stands as near a float32 reference as the fallback's:
PERF.md section 6, PR 39, `tools/chip_grad_precision.py`). A kernel's
gradient is rounded a shard of the batch's axes and summed over them at the
activations' dtype, as `ops/jax_ops.py` `_linear_dot` has every LINEAR
kernel's: the map is handed its weights ALREADY at that dtype (`handed`),
or the transpose of its copies would sum float32 (PERF.md section 6, PR 42:
0.4-0.8 % on those kernels' own leaves, none on any other).

What runs in the map is the diamond alone (the two linears, the activation
and the product); the trailing contraction, when the group swallowed one,
follows it as it always did, so its forward all-reduce is untouched.
Parameters keep their names, shapes and shardings.

`column_split` reads the group's pattern, its members' views and the mesh,
and says None for everything else, which then lowers as before.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Sequence

import jax

from flexflow_tpu.ffconst import OpType
from flexflow_tpu.parallel.sharding import (
    group_degree,
    prune_spec,
    spec_to_partition_spec,
)


class ColumnSplit(NamedTuple):
    axis: str           # the mesh axis the hidden dimension is split over
    batch: tuple        # the mesh axes the batch dimension is split over
    body: tuple         # the diamond's nodes, in the group's order
    rest: tuple         # what follows it inside the group (the tail)
    ext: tuple          # (guid, idx) of the diamond's one outside input
    out: tuple          # (guid, idx) of the product


def _by_column(axis: str) -> Dict[str, tuple]:
    """A linear's weights split by column over `axis`."""
    return {"kernel": ((), (axis,)), "bias": ((axis,),)}


def column_split(graph, mesh, members: Sequence) -> Optional[ColumnSplit]:
    """The split of a group this path takes, or None. Takes: pattern A of
    `Executor._find_hidden_groups` (two linears on one input, a unary on
    one of them, their product); every member's output split alike, its
    last dimension over one axis of the mesh longer than 1, its batch
    dimension over other axes or none, nothing between; the linears'
    kernels (and biases) split by column over that axis and no other; and
    an input that does not arrive split over it."""
    if mesh is None:
        return None
    product = next((i for i, n in enumerate(members)
                    if n.op_type == OpType.ELEMENT_BINARY), None)
    if product is None:
        return None         # patterns B and C: one linear, one gradient
    body, rest = tuple(members[:product + 1]), tuple(members[product + 1:])
    linears = [n for n in body if n.op_type == OpType.LINEAR]
    inside = {n.guid for n in body}
    ext = {(e.src, e.src_idx) for n in body for e in graph.in_edges(n)
           if e.src not in inside}
    if len(linears) != 2 or len(ext) != 1:
        return None
    specs = set()
    for n in body:
        spec = n.sharding.output_spec(0) if n.sharding is not None else None
        if spec is None:
            return None
        shape = tuple(d.size for d in n.outputs[0].dims)
        specs.add(prune_spec(spec, shape, mesh))
    if len(specs) != 1:
        return None
    (spec,) = specs
    if len(spec) < 2 or len(spec[-1]) != 1 or any(spec[1:-1]):
        return None         # unsplit, split over two axes, or a split
                            # sequence (seq_parallel: a reduce-scatter's)
    (axis,) = spec[-1]
    if mesh.shape[axis] < 2 or axis in spec[0]:
        return None
    want = _by_column(axis)
    for n in linears:
        for name, decl in n.attrs.weights(*graph.input_shapes(n)).items():
            got = prune_spec(n.sharding.weight_specs.get(name),
                             tuple(decl.shape.dims), mesh)
            if got != want.get(name):
                return None
    (ext_key,) = ext
    src = graph.node(ext_key[0]).sharding
    src_spec = src.output_spec(ext_key[1]) if src is not None else None
    if src_spec is not None and any(axis in axes for axes in src_spec):
        return None
    return ColumnSplit(axis, spec[0], body, rest, ext_key,
                       (body[-1].guid, 0))


def handed(w, x):
    """A weight as the map is handed it: ALREADY at the input's dtype, so
    that its gradient leaves the body at that dtype and the transpose of
    its copies sums it over the batch's axes at that dtype; the convert
    back to the master's is this `astype`'s transpose, after the sum."""
    return w.astype(x.dtype)


def run_split(split: ColumnSplit, mesh, lower: Callable, local: Dict,
              gparams: Dict) -> None:
    """Run the diamond per shard and leave its product in `local`.
    `lower(node, local, params, constrain)` lowers one node from `local`
    into it; inside the map nothing is constrained (the map's specs say
    how every value is split)."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.compat import shard_map

    axis, batch = split.axis, split.batch
    keys = [n.stable_key() for n in split.body]
    want = _by_column(axis)
    x = local[split.ext]

    def copies(value, axes):
        """A copy of `value` a shard of `axes`, along a new leading
        dimension split over them: nothing moves (each shard holds the
        value whole), and the transpose is the sum over those shards."""
        return jax.lax.broadcast(value, (group_degree(axes, mesh.shape),))

    def own(value):
        """A shard's own copy: the leading dimension is 1 in the body."""
        return value.reshape(value.shape[1:])

    def body(gp, xs):
        inner = {split.ext: own(xs)}
        gp = jax.tree.map(own, gp)
        for n in split.body:
            lower(n, inner, gp, False)
        return inner[split.out]

    params, specs = {}, {}
    for k in keys:
        # under the node's own scope: the sum over the batch's axes of a
        # kernel's gradient is read as that node's, as it was
        with jax.named_scope(k):
            params[k] = {name: copies(handed(w, x), batch)
                         for name, w in gparams[k].items()}
        specs[k] = {name: P(batch or None,
                            *spec_to_partition_spec(want[name]))
                    for name in gparams[k]}
    # under the first linear's scope: the one reduction of the input
    # gradient is read as that node's, as one of the two was
    with jax.named_scope(keys[0]):
        xs = copies(x, (axis,))
    rest = [None] * (x.ndim - 2)
    local[split.out] = shard_map(
        body, mesh, (specs, P(axis, batch or None, *rest)),
        P(batch or None, *rest, axis))(params, xs)
