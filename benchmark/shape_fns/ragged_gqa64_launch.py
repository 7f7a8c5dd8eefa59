"""What ONE launch of the ragged paged attention kernel needs, an ATTENTION
layer, in a model whose other layers are state-space ones, from the
counters the paged server leaves on its `launch_dispatch` span
(`flexflow_tpu/paged/scheduler.py`): `kv_pages`, the live pages the
launch's walks reach over (once a walk), and `qk_pairs`, the causal
(query, key) pairs.

Bytes: pages x page_size rows x 2 (K and V) x num_key_value_heads x the
head's width x the pool's itemsize, with the head's width hidden_size /
num_attention_heads (64: the pool keeps 64 lanes a head, and a pool padded
to 128 would read twice what is counted here). Queries, the new rows and
the output are left out (a few rows against whole pages).

Operations: 4 a pair a head's dim (2 a multiply-add, scores and values):
4 x pairs x num_attention_heads x the head's width. The kernel contracts
128 lanes where 64 carry values; what is needed is counted, not what is
done.

One (bytes, operations) pair an `attention` entry of the first
`num_hidden_layers` of the configuration's `layer_types`: the accepted
`span_roofline` reader would count every layer.
"""


def per_launch(attrs, cfg, itemsize):
    if "kv_pages" not in attrs or "qk_pairs" not in attrs:
        return None
    if "ssd_rows" not in attrs:
        return None     # not a graph of state-space layers beside attention
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    page = (cfg["server"]["page_size"] * 2 * cfg["num_key_value_heads"] * d
            * itemsize)
    pair = 4 * cfg["num_attention_heads"] * d
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(float(attrs["kv_pages"] * page), float(attrs["qk_pairs"] * pair))
            ] * kinds.count("attention")
