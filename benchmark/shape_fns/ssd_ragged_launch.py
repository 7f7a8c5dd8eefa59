"""What ONE launch of the state-space recurrence (`ssd_ragged_scan`) needs,
a Mamba-2 layer, from its `launch_dispatch` span
(`flexflow_tpu/paged/scheduler.py`: `state_slots`, the slots whose state
the launch touches, and `ssd_rows`, the live rows that go through a
state-space layer).

Bytes: a touched slot's state, heads x P x N float32, read once and
written once however many pieces of the slot's chunk the launch carries
(the kernel keeps it in VMEM while consecutive items name the same slot),
plus a live row's step-scaled input and its read-out (heads x P float32
each), its B and C (N float32 each: one group) and its step sizes (heads
float32). Pad rows are not needed bytes.

Operations: a live row and head, over the P x N state: the decay (1 a
value), the rank-one update S += (D x) B^T (2) and the read-out S C (2):
5 x P x N. That is the recurrence's work whatever solves it (a kernel that
solves an item's rows together does more arithmetic and is not credited
with it). The peak they are held against is the matrix unit's, as for
every kernel here.

Counts the layers whose kind is `mamba` only.
"""


def per_launch(attrs, cfg, itemsize):
    if "state_slots" not in attrs or "ssd_rows" not in attrs:
        return None
    heads, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    state = heads * p * n * 4
    row = (2 * heads * p + 2 * n + heads) * 4
    nbytes = 2 * attrs["state_slots"] * state + attrs["ssd_rows"] * row
    flops = attrs["ssd_rows"] * heads * 5 * p * n
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(float(nbytes), float(flops))] * kinds.count("mamba")
