"""What ONE launch of the grouped expert kernels needs, a layer, from the
counters the expert layers leave on its `launch_dispatch` span (lists, an
entry a layer; `flexflow_tpu/ops/expert_share.py` STATS).

Bytes: the weights of the held experts that at least one token reached
(`experts_hit`), each read once: 3 matrices of hidden_size x
moe_intermediate_size at the weights' 2 bytes (`torch_dtype` bfloat16).
Activations are left out (a few rows against 50 MB an expert).

Operations: 2 a multiply-add over the three matrices for every
assignment of a token to a held expert (`moe_assignments`): 6 x 4096 x
2048. Rows that pad a group to its tile are not needed operations.
"""


def per_launch(attrs, cfg, itemsize):
    if "experts_hit" not in attrs or "moe_assignments" not in attrs:
        return None
    matrix = cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return [(float(hit * 3 * matrix * 2), float(6 * assigned * matrix))
            for hit, assigned in zip(attrs["experts_hit"],
                                     attrs["moe_assignments"])]
