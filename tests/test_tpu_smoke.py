"""chip_smoke.py off the chip: its phases run tiny on the CPU with the
Pallas interpreter requested explicitly (FF_TPU_FLASH_INTERPRET=1), so the
checks it makes on the chip are themselves checked here; the script itself
always demands a TPU; and the one multi-chip serving case that cannot work
refuses in words."""

import dataclasses
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=512, dim=256, layers=2, heads=2, kv_heads=1,
            hidden=256, rope_theta=10000.0, norm_eps=1e-5)   # head_dim 128


@pytest.mark.parametrize("kv_dtype,page", [("auto", 16), ("int8", 32)])
def test_server_phase_tiny_interpreted(monkeypatch, kv_dtype, page):
    monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    size = chip_smoke.ServerSize(
        llama=TINY, slots=2, max_len=64, page_size=page, num_pages=9,
        prefill_chunk=8, prompt_lens=(3, 21), max_new=4)
    out = chip_smoke.server_phase(size, kv_dtype)
    assert out["kernel_variant"] == "ragged_pallas"
    assert out["steady_state_recompiles"] == 0
    assert out["greedy_check"]["tokens"] == 8
    assert out["failed_candidates"] == out["failed_measurements"] == 0
    if kv_dtype == "int8":
        assert out["kv_cache_dtype"] == "int8"


def test_trainer_phase_tiny_interpreted(monkeypatch):
    monkeypatch.setenv("FF_TPU_FLASH_INTERPRET", "1")
    size = chip_smoke.TrainerSize(llama=TINY, batch=2, seq=128, steps=3)
    out = chip_smoke.trainer_phase(size)
    assert out["attention_kernel"] == "pallas_flash"
    assert out["losses"][-1] < out["losses"][0]


def test_greedy_check_rejects_a_wrong_token(monkeypatch):
    """The check the server phase rests on must be able to fail."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=0, num_devices=1))
    build_llama(ff, LlamaConfig(**dict(TINY, dim=64, heads=2, hidden=64)),
                batch_size=1, seq_len=8, dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    prompt = np.arange(5, dtype=np.int32)
    good = ff.generate(prompt[None], 4)[0]
    ok = chip_smoke._check_greedy(ff, [prompt], [good], tie_tol=0.05)
    assert ok == {"tokens": 4, "exact_argmax": 4, "within_tie_tolerance": 0}
    bad = good.copy()
    bad[2] = (bad[2] + 1) % TINY["vocab_size"]
    with pytest.raises(AssertionError, match="below the reference argmax"):
        chip_smoke._check_greedy(ff, [prompt], [bad], tie_tol=0.05)


def test_chip_smoke_demands_the_chip():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit and no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=ROOT)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_chip_sizes_are_the_full_width():
    from flexflow_tpu.models.llama import LlamaConfig

    server, trainer = chip_smoke.chip_sizes()
    full = dataclasses.asdict(LlamaConfig.llama3_8b())
    assert {k: v for k, v in server.llama.items() if k != "layers"} \
        == {k: v for k, v in full.items() if k != "layers"}
    assert trainer.llama == dataclasses.asdict(LlamaConfig.bench_1b())
    assert (trainer.batch, trainer.seq) == (8, 1024)


def test_paged_serving_refuses_a_multichip_tpu_mesh(monkeypatch):
    """The pools are unsharded and the ragged pallas_call has no shard_map:
    on a multi-chip TPU mesh the server says so at construction instead of
    failing in the first trace (docs/serving.md)."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama

    ff = FFModel(FFConfig(batch_size=1, seed=0))      # all 8 CPU devices
    build_llama(ff, LlamaConfig.tiny(), batch_size=1, seq_len=8,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="ONE chip"):
        ff.serve_generation(slots=2, max_len=32, paged=True, page_size=8)
