"""The percentile helper, the interval union, and the traffic generators:
pure functions of the seed that give every seed the same sizes."""

import json
import os

import numpy as np
import pytest

import perfbench_helpers as h
from benchmark import serving
from benchmark.stats import median, percentile, union_length
from benchmark.traffic_kinds import closed_clients, open_poisson, \
    train_steps


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([5, 1, 4, 2, 3], 90, 4.6),
    ([10], 90, 10.0),
    ([1, 2], 0, 1.0),
    ([1, 2], 100, 2.0),
    (list(range(101)), 95, 95.0),
])
def test_percentile_matches_numpy(values, q, want):
    assert percentile(values, q) == pytest.approx(want)
    assert percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_refuses_nothing_and_a_bad_rank():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)
    assert median(np.array([3.0, 1.0, 2.0])) == 2.0


def test_union_length_counts_overlaps_once():
    assert union_length([]) == 0.0
    assert union_length([(0, 2), (1, 3), (5, 6)]) == 4.0
    assert union_length([(0, 10), (2, 3)]) == 10.0


def _traffic(name):
    with open(os.path.join(h.REPO, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def test_open_loop_schedule_is_a_pure_function_of_the_seed():
    t = _traffic("chat-steady")
    a = open_poisson.schedule(t, 2 ** 31 + 5, 45.0, 32768)
    b = open_poisson.schedule(t, 2 ** 31 + 5, 45.0, 32768)
    c = open_poisson.schedule(t, 7, 45.0, 32768)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [r.new_tokens for r in a] == [r.new_tokens for r in b]
    # another seed: the same trace of arrivals and sizes, other token ids
    assert [r.due_s for r in c] == [r.due_s for r in a]
    assert [(len(r.prompt), r.new_tokens) for r in c] == [
        (len(r.prompt), r.new_tokens) for r in a]
    assert not (a[0].prompt[:8] == c[0].prompt[:8]).all()
    assert len(a) == round(t["rate_rps"] * (t["ramp_s"] + 45.0))
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < t["ramp_s"] + 45
    for r in a:
        assert t["prompt_tokens"]["min"] <= len(r.prompt) \
            <= t["prompt_tokens"]["max"]
        assert t["new_tokens"]["min"] <= r.new_tokens \
            <= t["new_tokens"]["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 32768


def test_every_mix_fits_its_server():
    """No operation fails by construction: the longest request of each
    serving mix fits the server's `max_len`."""
    with open(os.path.join(h.REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    files = {c["name"]: c["file"] for c in doc["configs"]}
    for w in doc["workloads"]:
        with open(os.path.join(h.REPO, files[w["config"]])) as f:
            cfg = json.load(f)
        t = _traffic(w["traffic"])
        if "server" in cfg:
            assert (t["prompt_tokens"]["max"] + t["new_tokens"]["max"]
                    <= cfg["server"]["max_len"])


def test_length_distributions():
    rng = np.random.default_rng(0)
    u = serving.draw_lengths({"dist": "uniform", "min": 3, "max": 5}, 500,
                             rng)
    assert set(u) == {3, 4, 5}
    ln = serving.draw_lengths({"dist": "lognormal", "median": 256,
                               "sigma": 1.0, "min": 32, "max": 2048},
                              4000, rng)
    assert ln.min() >= 32 and ln.max() <= 2048
    assert 200 < np.median(ln) < 320
    with pytest.raises(ValueError):
        serving.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 1, rng)


def test_training_batches_come_from_the_seed():
    a = train_steps.batches(2 ** 31 + 1, 2, 8, 100)
    b = train_steps.batches(2 ** 31 + 1, 2, 8, 100)
    x1, y1 = next(a)
    x2, _ = next(a)
    assert (x1 == next(b)[0]).all() and not (x1 == x2).all()
    assert (y1[:, :-1] == x1[:, 1:]).all()


@pytest.mark.parametrize("stamps,want", [
    ((12.0, 14.0, 15.0), 110.0),    # all of it inside
    ((8.0, 12.0, 13.0), 60.0),      # half the prompt before the window
    ((18.0, 19.0, 21.0), 105.0),    # half the new tokens after it
    ((5.0, 9.0, 29.0), 5.0),        # decoding all through: 10 s of 20
    ((2.0, 5.0, 9.0), 0.0),         # done before it
    ((20.0, 22.0, 23.0), 0.0),      # admitted at its end
    ((12.0, 12.0, 12.0), 110.0),    # no span: counted at the instant
])
def test_tokens_served_inside_a_window_count_by_their_spans_share(stamps,
                                                                  want):
    """Window 10 -> 20 s; 100 prompt tokens over admit -> first token, 10
    new ones over first token -> done."""
    admit, first, done = (int(t * 1e9) for t in stamps)
    req = serving.Request(0, np.zeros(100, np.int32), 10, record={
        "admit_ns": admit, "first_token_ns": first, "done_ns": done})
    assert closed_clients.tokens_inside(req, 10.0, 20.0) == \
        pytest.approx(want)


def test_back_to_back_windows_share_out_every_token_once():
    req = serving.Request(0, np.zeros(777, np.int32), 33, record={
        "admit_ns": int(3.3e9), "first_token_ns": int(17.1e9),
        "done_ns": int(26.9e9)})
    parts = [closed_clients.tokens_inside(req, a, a + 10.0)
             for a in (0.0, 10.0, 20.0)]
    assert all(p > 0 for p in parts) and sum(parts) == pytest.approx(810.0)
