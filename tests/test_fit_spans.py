"""fit()'s loop on the span recorder: what an operator who turned
`obs.enable()` on sees of the host side of training, and that the loop pays
nothing for it while the recorder is off."""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer, obs
from flexflow_tpu.ffconst import DataType


@pytest.fixture(autouse=True)
def _recorder_off():
    obs.disable()
    yield
    obs.disable()


def _model(**cfg):
    ff = FFModel(FFConfig(batch_size=8, seed=0, epochs=2, **cfg))
    x = ff.create_tensor((8, 16), DataType.FLOAT, name="x")
    h = ff.dense(x, 32, name="d1")
    h = ff.relu(h)
    h = ff.dense(h, 4, name="d2")
    ff.softmax(h)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    return (ff, rng.standard_normal((32, 16)).astype(np.float32),
            rng.integers(0, 4, (32,)).astype(np.int32))


def _spans(rec):
    evs = [e for e in rec.events if e[4] and "id" in e[4]]
    by_id = {e[4]["id"]: e for e in evs}
    parent = lambda e: by_id.get(e[4]["parent"])  # noqa: E731
    return evs, parent


@pytest.mark.parametrize("loader", ["arrays", "dataloaders"])
def test_fit_spans_nest_under_the_epoch_and_cover_it(loader):
    ff, x, y = _model()
    ff.fit(x, y, verbose=False)         # compile outside the recording
    kw = {"x": x, "y": y}
    if loader == "dataloaders":
        kw = {"dataloaders": [
            ff.create_data_loader(None, x), ff.create_data_loader(None, y)]}
    # the last assertion is a share of wall time: on a machine whose cores
    # other processes share, the loop can be stalled between two spans, so
    # one recording in three has to show it (the structure, every time)
    for attempt in range(3):
        if loader == "dataloaders":
            for dl in kw["dataloaders"]:
                dl.reset()
        rec = obs.enable()
        ff.fit(verbose=False, **kw)
        obs.disable()
        evs, parent = _spans(rec)
        names = [e[0] for e in evs]
        assert names.count("epoch") == 2 and names.count("train_step") == 8
        assert names.count("epoch_sync") == 2
        # a wait a step and the one that finds the loader empty
        assert names.count("data_wait") == 10 and names.count("batch_put") == 8
        for e in evs:
            if e[0] in ("data_wait", "train_step", "epoch_sync"):
                assert parent(e)[0] == "epoch"
            if e[0] == "batch_put":         # inside the wait for its batch
                assert parent(e)[0] == "data_wait"
        # one beacon, rate-limited
        assert any(e[0] == "ffclock" for e in rec.events)
        covered = []
        for ep in (e for e in evs if e[0] == "epoch"):
            assert ep[4]["samples"] == 32
            inside = sum(e[2] for e in evs if e[4]["parent"] == ep[4]["id"])
            assert inside <= ep[2]
            covered.append(inside >= 0.5 * ep[2])    # the loop is its spans
        if all(covered):
            break
    assert all(covered)

def test_checkpoint_and_recompile_spans(tmp_path):
    from flexflow_tpu.runtime.recompile import RecompileState

    ff, x, y = _model(checkpoint_every=2, checkpoint_dir=str(tmp_path))
    state = RecompileState(lambda st: False, lambda st: None, ff)
    rec = obs.enable()
    ff.fit(x, y, epochs=1, verbose=False, recompile_state=state)
    obs.disable()
    evs, parent = _spans(rec)
    saves = [e for e in evs if e[0] == "checkpoint_save"]
    checks = [e for e in evs if e[0] == "recompile_check"]
    assert [e[4]["step"] for e in saves] == [2, 4]
    assert len(checks) == 4 and not any(e[4]["recompiled"] for e in checks)
    assert {parent(e)[0] for e in saves + checks} == {"epoch"}


def test_fit_with_the_recorder_off_builds_no_span(monkeypatch):
    """The guard tests/test_obs.py has for the serving tick, for fit():
    every site gets the shared null span, sets nothing, emits no beacon."""
    ff, x, y = _model()
    spans = []
    real_span = obs.span

    def watched(name):
        sp = real_span(name)
        spans.append((name, sp))
        return sp

    def forbidden(*a, **kw):
        raise AssertionError("built while tracing is off")

    monkeypatch.setattr(obs, "span", watched)
    monkeypatch.setattr(obs.trace.Span, "__init__", forbidden)
    monkeypatch.setattr(obs.trace._NullSpan, "set", forbidden)
    monkeypatch.setattr(obs.trace.TraceRecorder, "beacon", forbidden)
    monkeypatch.setattr(obs.trace.TraceRecorder, "instant", forbidden)
    ff.fit(x, y, verbose=False)
    assert {"epoch", "data_wait", "batch_put", "train_step",
            "epoch_sync"} <= {name for name, _sp in spans}
    assert all(sp is obs.NULL_SPAN for _name, sp in spans)
