"""The plain Mistral reference against the program, tiny, on the CPU: the
program's dense forward in float32 gives the reference's logits, the
trainer's loss the reference's loss, and the serving check can fail."""

import numpy as np
import pytest

import perfbench_helpers as h
from benchmark.families import mistral


@pytest.fixture(scope="module")
def tiny_f32():
    """build_llama at the tiny sizes in float32, one device."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import build_llama

    cfg = dict(h.TINY)
    ff = FFModel(FFConfig(batch_size=2, seed=3, num_devices=1))
    build_llama(ff, mistral.program_config(cfg), seq_len=32,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff, cfg, jax


def test_program_config_maps_every_published_key():
    lc = mistral.program_config(h.TINY)
    assert (lc.vocab_size, lc.dim, lc.layers, lc.heads, lc.kv_heads,
            lc.hidden, lc.rope_theta, lc.norm_eps) == (
                512, 256, 2, 2, 1, 256, 1e6, 1e-5)
    with pytest.raises(ValueError, match="sliding window"):
        mistral.program_config(dict(h.TINY, sliding_window=4096))
    with pytest.raises(ValueError, match="lacks"):
        mistral.program_config({"hidden_size": 8})


def test_reference_logits_equal_the_programs_forward(tiny_f32):
    ff, cfg, jax = tiny_f32
    ids = np.random.default_rng(0).integers(0, 512, (2, 32), dtype=np.int32)
    tr, ntr = ff._params
    probs = np.asarray(ff.executor.forward_fn()(tr, ntr, ids))
    w = mistral.reference_weights(tr, cfg)
    logits = jax.jit(mistral.reference_logits(cfg))
    for b in range(2):
        ref = np.asarray(jax.nn.softmax(logits(w, ids[b]), axis=-1))
        np.testing.assert_allclose(probs[b], ref, rtol=2e-4, atol=1e-7)
        assert (probs[b].argmax(-1) == ref.argmax(-1)).all()


def test_reference_loss_equals_the_trainers(tiny_f32):
    ff, cfg, jax = tiny_f32
    ids = np.random.default_rng(1).integers(0, 512, (2, 32), dtype=np.int32)
    labels = np.roll(ids, -1, axis=1)
    tr, ntr = ff._params
    prog = float(ff.executor.eval_step()(tr, ntr, labels, ids)["loss"])
    w = mistral.reference_weights(tr, cfg)
    loss = jax.jit(mistral.reference_loss(cfg))
    ref = float(np.mean([loss(w, ids[b], labels[b]) for b in range(2)]))
    assert prog == pytest.approx(ref, rel=1e-5)
    assert 5.0 < ref < 8.0      # near ln(512) at random weights


def test_required_flops_per_token_at_the_published_widths():
    import json
    import os

    with open(os.path.join(h.REPO, "benchmark", "configs",
                           "mistral-7b-train4.json")) as f:
        cfg = json.load(f)
    assert mistral.matmul_params(cfg) == 1442840576
    # 6 per parameter a token meets + causal attention at half density
    assert mistral.train_flops_per_token(cfg, 4096) == pytest.approx(
        9.2610e9, rel=1e-4)
    from benchmark.shape_fns import flash_causal_train

    need = flash_causal_train.per_chip_step(cfg)
    # 4 sequences x 16 heads x 6 layers x 6 * 4096^2 * 128 on one chip
    assert need["flops"] == 6.0 * 4096 ** 2 * 128 * 4 * 16 * 6
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
