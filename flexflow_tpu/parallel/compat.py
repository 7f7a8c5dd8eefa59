"""Thin wrappers over jax entry points the parallel subsystems share."""

from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs, check_vma: bool = True):
    """jax.shard_map with positional mesh/specs. check_vma=False opts out
    of the replication check — pallas_call outputs carry no
    varying-mesh-axes annotation."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def ensure_cpu_devices(n: int) -> None:
    """Force `n` virtual CPU devices (effective until the first backend
    initialization)."""
    jax.config.update("jax_num_cpu_devices", n)
