"""What ONE launch of the delta-rule scan (`kda_ragged_scan`) needs, a KDA
layer, from its `launch_dispatch` span (`flexflow_tpu/paged/scheduler.py`:
`state_slots`, the slots whose state the launch touches, and `kda_rows`,
the live rows that go through a state layer).

Bytes: a touched slot's state, heads x d_k x d_v float32, read once and
written once however many pieces of the slot's chunk the launch carries
(the kernel keeps it in VMEM while consecutive items name the same slot),
plus a live row's q, k, v, log-decay (heads x 128 float32 each), beta
(heads float32) and its output (heads x 128 float32). Pad rows are not
needed bytes.

Operations: a live row and head, over the 128 x 128 state: the decay (1 a
value), the read k^T S of the decayed state (2), the rank-one update
S += k d^T (2) and the read-out S^T q (2): 7 x 128 x 128 (ISSUE 44's 6
plus the read the update needs). The kernel solves a piece row by row, so
there are no further within-piece terms. They run on the vector unit; the
peak they are held against is the matrix unit's, as for every kernel here.

Counts the layers whose kind is KDA only.
"""

from benchmark.families import ling3 as fam


def per_launch(attrs, cfg, itemsize):
    if "state_slots" not in attrs or "kda_rows" not in attrs:
        return None
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    state = heads * d * d * 4
    row = heads * (5 * d + 1) * 4
    nbytes = 2 * attrs["state_slots"] * state + attrs["kda_rows"] * row
    flops = attrs["kda_rows"] * heads * 7 * d * d
    return [(float(nbytes), float(flops))] * fam.layer_kinds(cfg).count("kda")
