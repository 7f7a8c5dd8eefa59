"""Shared speculation fixtures for the tests.

Acceptance-quality numbers need a model whose greedy stream is
PREDICTABLE; an untrained model's argmax walk is arbitrary, so drafts
never match and every acceptance metric reads zero. The fixture here
makes prediction exact rather than hopeful.
"""

from __future__ import annotations


def make_token_cyclic(ff) -> None:
    """Make next-token a pure function of the CURRENT token: zero the
    attention output and MLP down projections in place, so the residual
    stream is just the token embedding. Greedy decode then settles into
    a cycle within at most vocab steps — a repetitive stream the n-gram
    drafter predicts perfectly once it has repeated once. Used by the
    >=1.5-accepted-tokens-per-step assertion (tests/test_spec.py)."""
    import jax.numpy as jnp

    tr, _ = ff._params
    for nk, ws in tr.items():
        if "wo" in ws:
            ws["wo"] = jnp.zeros_like(ws["wo"])  # fflint: host-ok (one-time fixture setup)
        if "_down_" in nk and "kernel" in ws:
            ws["kernel"] = jnp.zeros_like(ws["kernel"])  # fflint: host-ok (one-time fixture setup)
