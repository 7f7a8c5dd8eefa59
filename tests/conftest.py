"""Test configuration: force an 8-device CPU platform so multi-chip sharding
paths are exercised without TPU hardware (the reference's analog: multi-node
emulation via MPI ranks on one box, tests/multinode_helpers/; SURVEY.md
§4.5-4.6).

Both the env vars and jax.config are set: the env covers subprocesses the
tests spawn, jax.config covers this process (effective until the first
backend initialization)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# The persistent compilation cache (flexflow_tpu.runtime.compile_cache) is
# deliberately NOT enabled here: the suite must compile what it tests, and a
# cache shared between test processes would hide a lowering change behind a
# stale executable. It is placed by the entry points that run on the chip
# (chip_smoke.py, bench.py children, python -m flexflow_tpu).
