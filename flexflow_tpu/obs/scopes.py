"""The names the executor stamps into its compiled steps, and how to read
them back: the ONE place in the tree that reads a JAX name stack.

`Executor.run_forward` wraps every graph node in
`jax.named_scope(node.stable_key())`; `train_step()` / `eval_step()` wrap
the forward pass with its loss in `FORWARD`, the optimizer's update in
`OPTIMIZER` and the step's metrics in `STEP_METRICS`. JAX carries a scope
through its transforms by wrapping the name, so every instruction of the
optimized HLO (metadata `op_name`) and every event of a device trace that
keeps the `op_name` says which phase of the step it belongs to and which
node of the graph. What jax 0.9.0 writes (a CPU lowering; pinned by
tests/test_obs_scopes.py, so an upgrade that renames a wrapper fails a
test and is repaired in `classify` alone):

    jit(step)/jvp(forward)/l0_attn_5/dot_general                forward
    jit(step)/transpose(jvp(forward))/l0_attn_5/transpose       backward
    jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/
        rematted_computation/l0_gate_9/mul                      recompute
    jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/
        l0_gate_9/dot_general       backward (of a checkpointed body)
    jit(step)/optimizer/sub                                     optimizer
    jit(step)/forward/l0_attn_5/dot_general     forward (no grad: eval)

An XLA fusion carries ONE of its instructions' `op_name`; that is the
attribution a fused operation gets.

Below a node's key some lowerings name their own parts, for the serving
benchmark's `scope_share` reader: `hc_mix` (ops/hyper_connection.py),
`dsa_index` / `dsa_select` / `dsa_attend` (ops/latent_attention.py),
`ssd_proj` / `ssd_scan` (ops/mamba2.py: a Mamba-2 mixer outside and
inside its recurrence). Each is defined beside the code it wraps.

The SERVING step (`Executor.ragged_step_fn`) says besides what each node
is for. `run_forward` wraps a node's own scope in its GROUP, which the
executor derives from the node's `OpType` and its place in the graph
(`Executor.node_groups`), never from the spelling of its key; an attention
node on the paged path names its four PARTS (`ATTN_PARTS`, each a constant
beside the code it wraps: paged/attention.py); and what the step does
outside its nodes (the packed descriptor's slices, the fed ids, the launch
statistics' stacking) is under `UNPACK`. What jax 0.9.0 writes there (a
CPU lowering; pinned by tests/test_obs_scopes.py), read by
`classify_serving`:

    jit(step)/attn/l0_attn_1003/qkv/dot_general       attn  l0_attn_1003  qkv
    jit(step)/attn/l0_attn_1003/kv_write/scatter      attn  l0_attn_1003  kv_write
    jit(step)/attn/l0_attn_1003/attend/pallas_call    attn  l0_attn_1003  attend
    jit(step)/attn/l1_attn_7/attend/dsa_index/kv_write/scatter-add
                          attn  l1_attn_7  kv_write  (the innermost part)
    jit(step)/ffn/l0_gate_1007/dot_general            ffn   l0_gate_1007  -
    jit(step)/state/l0_mixer_12/ssd_scan/pallas_call  state l0_mixer_12   -
    jit(step)/unpack/slice                            glue  unpack        -
    jit(step)/l0_attn_1003/dot_general     no group: an executable from
                                           before the groups (a stale cache)

A collective is named besides by the mesh axes its replica groups span
(`group_axes`): an SPMD module's groups hold positions in the device
assignment, which is the mesh's devices flattened in the order of its
axes, so the axes are a pure function of the groups and the axis sizes.
Readers: `analysis/hloaudit.py` (the lowered module's collective
schedule) and the benchmark's `scope_time` reader (device time of a
traced training step by phase, node and axis).
"""

from __future__ import annotations

import re
from typing import List, Mapping, Optional, Sequence, Tuple

FORWARD = "forward"
OPTIMIZER = "optimizer"
STEP_METRICS = "step_metrics"
RECOMPUTE = "recompute"
BACKWARD = "backward"
PHASES = (FORWARD, RECOMPUTE, BACKWARD, OPTIMIZER, STEP_METRICS)

_TOP_SCOPES = (FORWARD, OPTIMIZER, STEP_METRICS)
# what jax.checkpoint names the second run of its body in the backward pass
_REMAT_MARK = "rematted_computation"
_TRANSPOSE_MARK = "transpose("
# `transpose(jvp(forward))` -> `forward`: a transform wraps the scope's name
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()+([^()]*)\)+$")
# scopes jax itself puts between a top-level scope and a node's
_JAX_SCOPES = ("checkpoint", _REMAT_MARK, "shard_map")

# the serving step: what a node is for, the parts of an attention node on
# the paged path (defined in paged/attention.py, beside what they wrap),
# and the step's own work outside its nodes
ATTN, FFN, EXPERTS, STATE, HEAD, GLUE = GROUPS = (
    "attn", "ffn", "experts", "state", "head", "glue")
ATTN_PARTS = ("qkv", "kv_write", "attend", "out")
UNPACK = "unpack"


def sorted_keys(node_keys: Sequence[str]) -> List[str]:
    """Longest first, so that `l0_attn_12` wins over a key that is its
    prefix. `classify` takes the keys in this order."""
    return sorted(node_keys, key=len, reverse=True)


def classify(op_name: str, node_keys: Optional[Sequence[str]] = None
             ) -> Tuple[Optional[str], Optional[str]]:
    """(phase, node key) of one `op_name`. `phase` is one of `PHASES`, or
    None for an instruction under none of the executor's top-level scopes
    (a program compiled without them, parameter plumbing).

    `node_keys`, longest first (`sorted_keys`): the first key found in the
    name is the node. Without them the node is read from the stack's
    shape: the first plain scope under the top-level one that is neither
    jax's own (`checkpoint`) nor the name's last part, which is the
    primitive."""
    parts = op_name.split("/")
    plain = [_WRAPPED.sub(r"\1", p) for p in parts]
    top = next((i for i, p in enumerate(plain) if p in _TOP_SCOPES), None)
    phase = None
    if top is not None:
        phase = plain[top]
        if phase == FORWARD:
            if _REMAT_MARK in parts:
                phase = RECOMPUTE
            elif any(p.startswith(_TRANSPOSE_MARK) for p in parts):
                phase = BACKWARD
    if node_keys is not None:
        return phase, next((k for k in node_keys if k in op_name), None)
    if top is None:
        return None, None
    # a transposed stack names the scope twice (`transpose(jvp(forward))/
    # jvp(forward)/checkpoint/...`): the node is under the last of them
    last = max(i for i, p in enumerate(plain) if p == plain[top])
    for i in range(last + 1, len(parts) - 1):
        if parts[i] == plain[i] and plain[i] not in _JAX_SCOPES:
            return phase, parts[i]
        if parts[i] != plain[i]:
            break       # a nested jit or transform: no node scope below
    return phase, None


def classify_serving(op_name: str
                     ) -> Tuple[Optional[str], Optional[str], Optional[str]]:
    """(group, node key, attention part) of one `op_name` of the serving
    step. The group is the first plain scope of the stack that is one of
    `GROUPS`, the node the scope under it, the part the INNERMOST of
    `ATTN_PARTS` below an attention node (a sparse layer writes its
    pooled keys, `kv_write`, inside `attend/dsa_index`). `UNPACK` reads
    as (`GLUE`, `UNPACK`, None). A stack that names a scope under
    `jit(...)` and no group gives (None, that scope, None): a node of a
    step compiled before the groups, which a reader reports. Anything
    else (another program, a parameter, no stack) is (None, None, None)."""
    parts = op_name.split("/")
    inner = parts[1:-1]         # between `jit(step)` and the primitive
    at = next((i for i, p in enumerate(inner)
               if p in GROUPS or p == UNPACK), None)
    if at is None:
        stray = inner[0] if inner and not _WRAPPED.match(inner[0]) else None
        return None, stray, None
    if inner[at] == UNPACK:
        return GLUE, UNPACK, None
    group, below = inner[at], inner[at + 1:]
    part = None
    if group == ATTN:
        part = next((p for p in reversed(below[1:]) if p in ATTN_PARTS),
                    None)
    return group, (below[0] if below else None), part


# ---------------------------------------------------------------------------
# replica groups -> mesh axes

_GROUPS_LIST = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_ONE_GROUP = re.compile(r"\{([\d,]*)\}")


def _ints(text: str) -> List[int]:
    return [int(t) for t in text.split(",") if t]


def replica_groups(hlo_line: str) -> Optional[List[List[int]]]:
    """The device groups of one collective's HLO line, in either form XLA
    prints: explicit (`replica_groups={{0,1},{2,3}}`) or iota
    (`replica_groups=[2,2]<=[4]`, `[2,2]<=[2,2]T(1,0)`: the ids 0..n-1
    shaped, transposed, then cut into G groups of S). An empty list is
    XLA's "every device in one group"; None means the line names no
    replica groups."""
    m = _GROUPS_IOTA.search(hlo_line)
    if m:
        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = _ints(m.group(3))
        perm = _ints(m.group(4)) if m.group(4) else list(range(len(dims)))
        ids = _iota_transposed(dims, perm)
        return [ids[g * size:(g + 1) * size] for g in range(n_groups)]
    m = _GROUPS_LIST.search(hlo_line)
    if m:
        return [_ints(g) for g in _ONE_GROUP.findall(m.group(1))]
    return None


def collective_groups(hlo_line: str) -> Optional[List[List[int]]]:
    """`replica_groups`, or a collective-permute's `source_target_pairs`
    read as groups of two: what `group_axes` needs of either."""
    groups = replica_groups(hlo_line)
    if groups is None:
        m = _PAIRS.search(hlo_line)
        if m:
            return [_ints(g) for g in _ONE_GROUP.findall(m.group(1))]
    return groups


def _iota_transposed(dims: Sequence[int], perm: Sequence[int]) -> List[int]:
    """arange(prod(dims)).reshape(dims).transpose(perm).ravel(), without
    numpy: this module is imported by the executor."""
    strides = [1] * len(dims)
    for i in range(len(dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * dims[i + 1]
    out = [0]
    for axis in perm:
        out = [base + k * strides[axis] for base in out
               for k in range(dims[axis])]
    return out


def group_axes(groups: Optional[Sequence[Sequence[int]]],
               mesh_axes: Mapping[str, int]) -> Tuple[str, ...]:
    """The mesh axes that one collective's groups span, in the mesh's
    order. `mesh_axes` is {axis name: size} in the order the mesh was
    built with; a position p in the device assignment has the coordinates
    of p unravelled over those sizes, and a group spans an axis when its
    members differ along it. No groups at all (None) spans nothing; an
    empty list is XLA's "all devices" and spans every axis longer than 1."""
    if groups is None:
        return ()
    names, sizes = list(mesh_axes), [int(s) for s in mesh_axes.values()]
    if not groups:
        return tuple(n for n, s in zip(names, sizes) if s > 1)
    spanned = set()
    for group in groups:
        coords = [_unravel(p, sizes) for p in group]
        for a in range(len(sizes)):
            if len({c[a] for c in coords}) > 1:
                spanned.add(a)
    return tuple(names[a] for a in sorted(spanned))


def _unravel(position: int, sizes: Sequence[int]) -> List[int]:
    out = []
    for s in reversed(sizes):
        out.append(position % s)
        position //= s
    return out[::-1]


def axes_label(axes: Sequence[str]) -> str:
    """`model`, `data`, `data+model`, or `none` (a collective over one
    device, or a line without groups)."""
    return "+".join(axes) if axes else "none"
