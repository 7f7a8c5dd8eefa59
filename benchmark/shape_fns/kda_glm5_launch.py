"""`kda_ragged_launch` for a configuration of the GLM-5.3-Flash family:
the same bytes and operations a launch of the delta-rule scan (see that
file), with the heads and the head size read where THIS family's
`config.json` keeps them (`linear_attn_config.num_heads` / `.head_dim`;
its top-level `head_dim` is 0 and it has no `layer_group_size`, which the
accepted function reads through `families/ling3.py`), counted once a
layer whose published `layer_types` entry is linear. PERF.md section 7
hands the merge of the two to the next `benchmark` issue."""

from benchmark.families import glm5 as fam
from benchmark.shape_fns import kda_ragged_launch

# the accepted function's own keys for ONE linear layer
_ONE_KDA_LAYER = {"first_layer": 0, "layer_group_size": 2,
                  "num_hidden_layers": 1}


def per_launch(attrs, cfg, itemsize):
    lin = cfg["linear_attn_config"]
    need = kda_ragged_launch.per_launch(
        attrs, dict(_ONE_KDA_LAYER, num_attention_heads=lin["num_heads"],
                    head_dim=lin["head_dim"]), itemsize)
    if need is None:
        return None
    return need * fam.layer_kinds(cfg).count("kda")
