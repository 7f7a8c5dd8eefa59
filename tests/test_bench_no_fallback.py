"""bench.py measures this run on the chip or fails: no cached result, no
retried probe, no guessed peak, no exit 0 after a failed side. Also the
pieces it shares with the other chip entry points — the device_kind chip
table (search/machine_model.py) and the compile-cache placement helper
(runtime/compile_cache.py). Parent-side logic runs in-process with the
child spawn faked; one test runs the real thing under JAX_PLATFORMS=cpu."""

import importlib.util
import json
import logging
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_no_chip_is_nonzero_exit_and_no_result_line():
    """The real parent, the real child, no accelerator: the side refuses
    the platform, the parent exits non-zero, and stdout carries no JSON —
    in particular no line from an earlier run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLEXFLOW_BENCH_SMOKE", None)
    env.pop("FLEXFLOW_BENCH_PLATFORM", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py"),
                        "--config", "200m"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []
    assert "not a TPU" in p.stderr


def test_last_green_machinery_is_gone():
    bench = _load_bench()
    for name in ("_persist_green", "_emit_last_green_or", "_GREEN_PATH",
                 "_probe_backend", "_probe_main"):
        assert not hasattr(bench, name), name
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert "last_green" not in f.read()


def _fake_run(returncode=0, stdout=""):
    def run(cmd, **kw):
        return types.SimpleNamespace(returncode=returncode, stdout=stdout)
    return run


def test_failed_side_exits_nonzero_once(monkeypatch, capsys):
    """One attempt per side: a child that fails ends the run."""
    bench = _load_bench()
    calls = []

    def run(cmd, **kw):
        calls.append(cmd)
        return types.SimpleNamespace(returncode=1, stdout="")

    monkeypatch.setattr(subprocess, "run", run)
    with pytest.raises(SystemExit) as e:
        bench._spawn_side("framework", "200m", timeout=60)
    assert e.value.code not in (0, None)
    assert len(calls) == 1
    assert _json_lines(capsys.readouterr().out) == []


def test_hung_or_silent_side_exits_nonzero(monkeypatch):
    bench = _load_bench()

    def hang(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout"))

    monkeypatch.setattr(subprocess, "run", hang)
    with pytest.raises(SystemExit) as e:
        bench._spawn_side("naive", "1b", timeout=60)
    assert e.value.code not in (0, None)
    monkeypatch.setattr(subprocess, "run", _fake_run(0, "\n"))
    with pytest.raises(SystemExit) as e:
        bench._spawn_side("naive", "1b", timeout=60)
    assert e.value.code not in (0, None)


def test_failed_1b_is_nonzero_even_after_a_200m_line(monkeypatch, capsys):
    """The default path prints the 200m line this run measured, then a
    failed 1b ends the run non-zero — it used to return 0."""
    bench = _load_bench()
    facts = {"platform": "tpu", "device_kind": "TPU v5 lite",
             "n_devices": 1}

    def spawn_side(side, config, timeout):
        if config == "1b":
            sys.exit("side framework/1b failed (rc=1)")
        return {"tokens_per_sec": 1000.0 if side == "framework" else 500.0,
                **facts}

    monkeypatch.setattr(bench, "_spawn_side", spawn_side)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("FLEXFLOW_BENCH_SMOKE", raising=False)
    monkeypatch.delenv("FLEXFLOW_BENCH_CONFIG", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    lines = [json.loads(ln) for ln in _json_lines(capsys.readouterr().out)]
    assert [ln["metric"] for ln in lines] == [
        "llama_200m_train_tokens_per_sec"]
    # every result line names the device it ran on
    assert {k: lines[0][k] for k in facts} == facts
    assert lines[0]["vs_baseline"] == 2.0 and "mfu" in lines[0]


def test_unknown_device_kind_is_an_error():
    """One chip table for the bench's peak and the search's machine
    model; a kind that is not in it raises instead of assuming a v5e."""
    from flexflow_tpu.search.machine_model import chip_for_device_kind

    bench = _load_bench()
    assert bench._peak_flops("TPU v5 lite", 4) == 4 * 197e12
    assert bench._peak_flops("TPU v5p", 1) == 459e12
    assert chip_for_device_kind("TPU v5") == "v5p"
    assert chip_for_device_kind("TPU v4") == "v4"
    assert chip_for_device_kind("TPU v6 lite") == "v6e"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        bench._peak_flops("TPU v9 mega", 1)
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        chip_for_device_kind("cpu")


def test_search_models_the_attached_chip(caplog):
    """The strategy search prices the chip JAX attached; off a TPU it
    models a v5e and says so."""
    from flexflow_tpu.search.machine_model import chip_for_device

    dev = types.SimpleNamespace
    assert chip_for_device(dev(platform="tpu",
                               device_kind="TPU v5 lite")) == "v5e"
    assert chip_for_device(dev(platform="tpu", device_kind="TPU v5p")) \
        == "v5p"
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        chip_for_device(dev(platform="tpu", device_kind="TPU v9 mega"))
    with caplog.at_level(logging.INFO,
                         logger="flexflow_tpu.search.machine_model"):
        assert chip_for_device(dev(platform="cpu",
                                   device_kind="cpu")) == "v5e"
    assert "modelling a v5e" in caplog.text


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, nothing is set
    in code. Unset: the one fixed path inside the checkout."""
    import jax

    from flexflow_tpu.runtime import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    assert compile_cache.enable_compile_cache() == want  # never moves
