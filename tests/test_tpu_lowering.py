"""Every Pallas entry point lowers for TPU from the CPU, at the chip
smoke's shapes (tools/chip_kernels.py holds the catalog). `jax.export`
with platforms=["tpu"] runs the Pallas->Mosaic lowering on any host, so
a block-shape refusal fails here, in tier-1, not on the chip. What the
Mosaic compiler then accepts is the chip run's to say."""

import jax
import pytest

from tools.chip_kernels import kernel_cases

# the ring case shards over the 8 virtual CPU devices conftest forces
CASES = kernel_cases(n_devices=4)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu(name):
    fn, args, _ref = CASES[name]()
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()
