"""Native C++ search engine (native/ffsim.cc) vs the Python cost model."""

import numpy as np
import pytest

import flexflow_tpu as fx
from flexflow_tpu import native
from flexflow_tpu.search import space
from flexflow_tpu.search.cost_model import CostModel, graph_cost
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.mcmc import mcmc_optimize
from flexflow_tpu.search.table import build_table


def _mlp_graph():
    ff = fx.FFModel(fx.FFConfig(batch_size=64))
    x = ff.create_tensor((64, 512), fx.DataType.FLOAT)
    h = ff.dense(x, 2048, name="fc1")
    h = ff.relu(h)
    h = ff.dense(h, 2048, name="fc2")
    h = ff.dense(h, 64, name="fc3")
    ff.softmax(h)
    return ff.graph


def _cost():
    machine = TPUMachineModel.make("v5e", num_chips=8)
    return CostModel(machine, {"data": 4, "model": 2})


def test_native_builds():
    assert native.available(), "g++ build of libffsim.so failed"
    assert native.engine().startswith("native")


def test_stale_library_is_rebuilt_not_loaded(tmp_path):
    """The rebuild is keyed on a hash of the source, not on file times (a
    copy or a checkout scrambles those): a library whose sidecar hash
    differs from the source — or that has no sidecar at all, like one
    built before this rule — is rebuilt."""
    import ctypes
    import os

    src = tmp_path / "answer.cc"
    lib = str(tmp_path / "libanswer.so")

    def answer():
        fn = ctypes.CDLL(lib).answer
        fn.restype = ctypes.c_int
        return fn()

    src.write_text('extern "C" int answer() { return 1; }\n')
    assert native._build_so(str(src), lib)
    stamp = lib + ".src-sha256"
    assert open(stamp).read().strip() == native._src_hash(str(src))
    built = os.stat(lib).st_ino
    assert native._build_so(str(src), lib)          # same source: reused
    assert os.stat(lib).st_ino == built

    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(lib, (2e9, 2e9))                       # library looks NEWER
    assert native._build_so(str(src), lib)
    assert os.stat(lib).st_ino != built             # rebuilt all the same
    assert open(stamp).read().strip() == native._src_hash(str(src))
    assert answer() == 2

    os.remove(stamp)                                # pre-rule library
    built = os.stat(lib).st_ino
    assert native._build_so(str(src), lib)
    assert os.stat(lib).st_ino != built


def test_table_matches_graph_cost():
    graph, cost = _mlp_graph(), _cost()
    candidates = {
        n.name: space.enumerate_views(n, cost.axis_sizes)
        for n in graph.nodes
        if len(space.enumerate_views(n, cost.axis_sizes)) > 1
    }
    base = space.default_dp_strategy(graph, cost.axis_sizes)
    table = build_table(graph, cost, candidates, base)

    # assignment -> strategy dict -> graph_cost must equal table.eval
    rng = np.random.RandomState(0)
    for _ in range(10):
        a = [rng.randint(len(v)) for v in table.views]
        strategy = dict(base)
        strategy.update(table.to_strategy(a))
        t_tab, m_tab = table.eval(a)
        gc = graph_cost(graph, strategy, cost)
        assert t_tab == pytest.approx(gc.time, rel=1e-9)
        assert m_tab == pytest.approx(gc.memory_per_chip, rel=1e-9)


def test_native_eval_matches_python():
    graph, cost = _mlp_graph(), _cost()
    candidates = {
        n.name: space.enumerate_views(n, cost.axis_sizes)
        for n in graph.nodes
        if len(space.enumerate_views(n, cost.axis_sizes)) > 1
    }
    base = space.default_dp_strategy(graph, cost.axis_sizes)
    table = build_table(graph, cost, candidates, base)
    g = table.to_native()
    rng = np.random.RandomState(1)
    for _ in range(20):
        a = [rng.randint(len(v)) for v in table.views]
        t_py, m_py = table.eval(a)
        t_c, m_c = g.eval(a)
        assert t_c == pytest.approx(t_py, rel=1e-12)
        assert m_c == pytest.approx(m_py, rel=1e-12)


def test_native_mcmc_improves_over_start():
    graph, cost = _mlp_graph(), _cost()
    strategy = mcmc_optimize(graph, cost, budget=500, seed=3)
    base = space.default_dp_strategy(graph, cost.axis_sizes)
    t_found = graph_cost(graph, {**base, **strategy}, cost).time
    t_base = graph_cost(graph, base, cost).time
    assert t_found <= t_base


def test_native_simulate_sane():
    """Event-driven makespan is at least the compute critical path and at
    most the fully-serialized sum."""
    graph, cost = _mlp_graph(), _cost()
    base = space.default_dp_strategy(graph, cost.axis_sizes)
    table = build_table(graph, cost, {}, base)
    g = table.to_native()
    a = [0] * len(table.nodes)
    mk = g.simulate(a)
    serial, _ = table.eval(a, overlap=0.0)
    compute_only = sum(table.compute[i][0] for i in range(len(table.nodes)))
    assert compute_only <= mk <= serial + 1e-12


def test_python_fallback_matches_native_strategy_quality(monkeypatch):
    graph, cost = _mlp_graph(), _cost()
    s_native = mcmc_optimize(graph, cost, budget=400, seed=5)
    monkeypatch.setenv("FLEXFLOW_NATIVE", "0")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    s_py = mcmc_optimize(graph, cost, budget=400, seed=5)
    monkeypatch.setattr(native, "_tried", False)
    base = space.default_dp_strategy(graph, cost.axis_sizes)
    t_n = graph_cost(graph, {**base, **s_native}, cost).time
    t_p = graph_cost(graph, {**base, **s_py}, cost).time
    # different RNGs, same space: both must at least match the DP baseline
    t_base = graph_cost(graph, base, cost).time
    assert t_n <= t_base and t_p <= t_base
