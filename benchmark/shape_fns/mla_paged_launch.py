"""What ONE launch of the latent paged attention kernel needs, a layer,
from its `launch_dispatch` span (`flexflow_tpu/paged/scheduler.py`).

Bytes: `latent_pages`, the live latent pages the launch has to read, each
counted ONCE a slot however many pieces of the slot's chunk walk them (a
kernel that read a prefix once must not read over 100 %), times page_size
x (kv_lora_rank + qk_rope_head_dim) values x the pool's itemsize. The
row's pad lanes (320 -> 384) are not needed bytes. Queries, the new rows
and the output are left out (a few rows against whole pages).

Operations: `qk_pairs`, the causal (query row, key) pairs, times heads x
2 x (latent width for the score + kv_lora_rank for the value):
32 x 2 x (320 + 256) = 36,864 a pair in the absorbed form.
"""


def per_launch(attrs, cfg, itemsize):
    if "latent_pages" not in attrs or "qk_pairs" not in attrs:
        return None
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    nbytes = (attrs["latent_pages"] * cfg["server"]["page_size"] * width
              * itemsize)
    flops = (attrs["qk_pairs"] * cfg["num_attention_heads"] * 2
             * (width + cfg["kv_lora_rank"]))
    return [(float(nbytes), float(flops))] * cfg["num_hidden_layers"]
