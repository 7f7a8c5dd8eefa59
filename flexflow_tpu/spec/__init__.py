"""Speculative decoding subsystem (serve_generation(paged=True,
speculate=SpecConfig(...))).

Layered on flexflow_tpu.paged — verifying a TREE of drafted tokens in
one model step instead of one token per step, the
search-over-structure spirit of the source paper applied to inference:

  config.py   SpecConfig (drafter choice, tree width/depth)
  drafter.py  pluggable drafters: n-gram prompt-lookup (zero weights,
              CPU-testable) and a small-draft-model drafter driven
              through a second Executor
  tree.py     token-tree trie, flattened ancestor masks, greedy accept
  server.py   SpeculativePagedServer: draft -> tree-verify -> commit

The tree-verify attention itself (Pallas kernel + gather fallback) lives
in flexflow_tpu.paged.attention next to the decode kernel it extends;
the jitted step functions are Executor.ragged_step_fn / paged_commit_fn.
See docs/speculative.md.
"""

from flexflow_tpu.spec.config import SpecConfig
from flexflow_tpu.spec.drafter import (
    Drafter,
    DraftModelDrafter,
    NgramDrafter,
)
from flexflow_tpu.spec.server import SpeculativePagedServer
from flexflow_tpu.spec.tree import (
    TokenTree,
    accept_greedy,
    ancestor_masks,
    build_tree,
)

__all__ = [
    "SpecConfig",
    "Drafter",
    "NgramDrafter",
    "DraftModelDrafter",
    "SpeculativePagedServer",
    "TokenTree",
    "build_tree",
    "ancestor_masks",
    "accept_greedy",
]
