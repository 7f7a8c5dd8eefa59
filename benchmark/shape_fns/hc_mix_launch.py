"""What ONE launch needs of the residual mixing (`hc_mix`: a path of
hc_mult streams mixed before and after every block), from its
`launch_dispatch` span's `hc_rows`, live rows times mixings
(`flexflow_tpu/paged/scheduler.py` `_sparse_counts`).

Bytes: a live row's hc_mult x hidden_size stream values read once and
written once a block, at the activations' 2 bytes (the block's own input
and output, one stream wide, and the 24 coefficients a row are left
out). Operations: none that a matrix unit would do (a 16,384 x 24
projection and a 4 x 4 mix a row): the mixing is bound by bytes, and the
pair says so with 0.

One entry a launch: `hc_rows` already counts every mixing.
"""


def per_launch(attrs, cfg, itemsize):
    if "hc_rows" not in attrs:
        return None
    width = cfg["hc_mult"] * cfg["hidden_size"]
    return [(float(attrs["hc_rows"] * 2 * width * itemsize), 0.0)]
