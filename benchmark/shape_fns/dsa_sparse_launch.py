"""What ONE launch needs of a SPARSE latent layer (an indexer over pooled
keys, a selection, attention over the selected tokens), a sparse layer,
from its `launch_dispatch` span (`flexflow_tpu/paged/scheduler.py`
`_sparse_counts`, and `selected_distinct`, counted on the device where
the selection is made). It counts the SPARSE form's work whatever
implements it: a walk of every live page that masks what was not selected
reads and multiplies more, and reads as a small share of this.

Bytes: the pooled keys of the pages the launch's slots hold, each page
ONCE a slot (`index_pages` x page_size / index_kpool rows x
index_head_dim x itemsize), plus the latent rows the launch's rows
selected, each ONCE a slot however many of the slot's rows chose it
(`selected_distinct` x kv_lora_rank x itemsize): a kernel that reads a
block once for several rows must not read over 100 %, the rule
`mla_paged_launch.py` states for pages.

Operations: a (row, pooled key) pair scored by every indexer head
(`index_blocks_scored` x index_n_heads x index_head_dim x 2), plus a
selected token's score and value over every head in the absorbed form,
kv_lora_rank wide each (`selected_tokens` x num_attention_heads x 2 x
(kv_lora_rank + kv_lora_rank): no rope part).

Counts the layers whose kind is sparse only.
"""

from benchmark.families import glm5 as fam

KEYS = ("index_pages", "selected_distinct", "index_blocks_scored",
        "selected_tokens")


def per_launch(attrs, cfg, itemsize):
    if any(k not in attrs for k in KEYS):
        return None
    page = cfg["server"]["page_size"]
    latent = cfg["kv_lora_rank"]
    pooled_page = page // cfg["index_kpool"] * cfg["index_head_dim"]
    scored = (attrs["index_blocks_scored"] * cfg["index_n_heads"]
              * cfg["index_head_dim"] * 2)
    attended = (attrs["selected_tokens"] * cfg["num_attention_heads"] * 2
                * 2 * latent)
    return [(float((attrs["index_pages"] * pooled_page + distinct * latent)
                   * itemsize), float(scored + attended))
            for distinct in attrs["selected_distinct"]][
                :fam.layer_kinds(cfg).count("dsa")]
