"""The command itself: with no TPU attached it exits non-zero and prints no
result line; so does the sweep; an unknown cell is refused."""

import os
import subprocess
import sys

import pytest

import perfbench_helpers as h


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(h.REPO, "benchmark", script), *args],
        env=env, capture_output=True, text=True, timeout=300, cwd=h.REPO)


@pytest.mark.parametrize("cell", ["mistral-7b-serve1.chat-steady",
                                  "mistral-7b-train4.pretrain-seq4096"])
def test_the_command_demands_the_chip(cell):
    p = _run("run.py", "--workload", cell, "--seed", str(2 ** 31 + 3),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert "TPU chip" in p.stderr


def test_the_sweep_demands_the_chip():
    p = _run("sweep.py", "--workload", "mistral-7b-serve1.chat-steady",
             "--rates", "1")
    assert p.returncode != 0 and "TPU chip" in p.stderr


def test_an_unknown_cell_is_refused():
    p = _run("run.py", "--workload", "no-such.cell", "--seed", "1",
             "--seconds", "1")
    assert p.returncode == 2 and "no cell" in p.stderr
    assert '"metrics"' not in p.stdout


def test_a_directory_with_only_the_benchmark_is_refused(tmp_path):
    """BENCHMARK.json and the files under `paths` alone, without the
    program: non-zero exit, no result line."""
    import shutil

    shutil.copy(os.path.join(h.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(h.REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b-serve1.chat-steady", "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert p.returncode != 0 and '"metrics"' not in p.stdout
    assert "not in this checkout" in p.stderr
