"""`mla_paged_launch` for a graph whose layers are not all latent: the same
bytes and operations a launch of the latent paged attention kernel (see
that file), counted once a layer whose kind is MLA instead of
`num_hidden_layers` times (one of seven in `ling-3-flash-serve1`; the
accepted function would read seven times the truth there)."""

from benchmark.families import ling3 as fam
from benchmark.shape_fns import mla_paged_launch


def per_launch(attrs, cfg, itemsize):
    need = mla_paged_launch.per_launch(attrs, cfg, itemsize)
    if need is None:
        return None
    return need[:1] * fam.layer_kinds(cfg).count("mla")
