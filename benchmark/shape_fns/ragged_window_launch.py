"""What ONE launch of the ragged paged attention kernel needs, a layer,
where sliding-window layers stand beside full ones, from the counters the
paged server leaves on its `launch_dispatch` span by CLASS of layer
(`flexflow_tpu/paged/scheduler.py` `_window_counts`): `kv_pages_full` /
`kv_pages_window`, the live pages the launch has to read in a layer of
that class (in a window layer from the window of a slot's first query to
its last row), each counted ONCE a slot however many pieces of the slot's
chunk walk them (a kernel that read a prefix once must not read over
100 %), and `qk_pairs_full` / `qk_pairs_window`, the visible (query, key)
pairs (min(position + 1, sliding_window) keys a query in a window layer).

Bytes: pages x page_size rows x 2 (K and V) x num_key_value_heads x
head_dim values x the pool's itemsize. Queries, the new rows and the
output are left out (a few rows against whole pages).

Operations: 4 a pair a head's dim (2 a multiply-add, scores and values):
4 x pairs x num_attention_heads x head_dim.

One (bytes, operations) pair a layer, by the first `num_hidden_layers`
entries of the configuration's `layer_types`.
"""


def per_launch(attrs, cfg, itemsize):
    keys = ("kv_pages_full", "kv_pages_window", "qk_pairs_full",
            "qk_pairs_window")
    if any(k not in attrs for k in keys):
        return None
    page = (cfg["server"]["page_size"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)
    pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    need = {
        "full_attention": (float(attrs["kv_pages_full"] * page),
                           float(attrs["qk_pairs_full"] * pair)),
        "sliding_attention": (float(attrs["kv_pages_window"] * page),
                              float(attrs["qk_pairs_window"] * pair)),
    }
    return [need[kind]
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]
