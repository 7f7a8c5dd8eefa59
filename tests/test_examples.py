"""Smoke-run every example script (the reference's multi_gpu_tests.sh
pattern: examples ARE the integration suite) on the virtual CPU mesh."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(ROOT, "examples", "python")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # the CLI driver places the persistent compile cache; the suite
    # compiles what it tests, so the cache stays off here
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def _run(script, *flags, timeout=420):
    # the CLI driver's --platform flag configures the backend before any
    # jax touch
    p = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu", "--platform", "cpu",
         "--cpu-devices", "8", os.path.join(EX, script), "-e", "1", *flags],
        env=_env(), capture_output=True, text=True, timeout=timeout,
    )
    assert p.returncode == 0, f"{script} failed:\n{p.stdout}\n{p.stderr}"
    return p.stdout


@pytest.mark.parametrize("script,flags", [
    ("mnist_mlp.py", ("-b", "64")),
    ("alexnet_cifar10.py", ("-b", "32")),
    ("llama_train.py", ("-b", "4", "--mesh", "data=2,model=4")),
    ("llama_train.py", ("-b", "4", "--budget", "8", "--mesh", "data=2,model=4")),
    ("bert_attribute_parallel.py", ("-b", "8", "--mesh", "data=2,model=4")),
    ("mixtral_moe.py", ("-b", "8", "--mesh", "data=2,expert=4")),
    ("resnet_torch_import.py", ("-b", "8",)),
    ("hf_finetune.py", ("-b", "4",)),
    ("inception_v3.py", ("-b", "4",)),
    ("candle_uno.py", ("-b", "16",)),
    ("dlrm_train.py", ("-b", "32",)),
    ("nmt_seq2seq.py", ("-b", "32", "--mesh", "data=2,model=4")),
    ("transformer.py", ("-b", "8",)),
    ("transformer.py", ("-b", "8", "--enc-dec")),
])
def test_example_runs(script, flags):
    out = _run(script, *flags)
    assert "epoch 0" in out or "samples=" in out


def test_cli_driver():
    p = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu", "--platform", "cpu",
         os.path.join(EX, "mnist_mlp.py"), "-b", "64", "-e", "1"],
        env=_env(), capture_output=True, text=True, timeout=420,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "samples=" in p.stdout
