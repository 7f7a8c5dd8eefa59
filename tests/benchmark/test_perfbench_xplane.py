"""The reduction from a profiler trace to busy time, time by operation,
collective time and idle gaps by host span: first on events written out by
hand, where every number can be checked in the head, then on a small trace
recorded on the chip (tests/benchmark/data/)."""

import os

import pytest

from benchmark import xplane

MS = 1e6  # nanoseconds


def _hand_trace():
    dev0 = [("fusion.1", 0 * MS, 2 * MS),
            ("ragged_paged_attention", 2 * MS, 1 * MS),
            ("while", 5 * MS, 4 * MS),            # a parent ...
            ("all-reduce.3", 6 * MS, 1 * MS),     # ... and its children
            ("fusion.1", 7 * MS, 2 * MS)]
    dev1 = [("fusion.1", 0 * MS, 5 * MS)]
    host = {"loop": [("decode_tick", 0 * MS, 3.2 * MS),
                     ("tick_prep", 3.2 * MS, 1.7 * MS),
                     ("admit_pending", 3.5 * MS, 1.0 * MS),
                     ("not_ours", 0, 10 * MS)]}
    return {0: dev0, 1: dev1}, host


def test_reduction_of_a_hand_written_trace():
    devices, host = _hand_trace()
    red = xplane.reduce_events(
        devices, host, span_names=("decode_tick", "tick_prep",
                                   "admit_pending"),
        window=(0.0, 10 * MS))
    assert red["chips"] == 2 and red["window_s"] == pytest.approx(0.010)
    c0, c1 = red["per_chip"][0], red["per_chip"][1]
    # chip 0 is busy 0-3 and 5-9: nested operations count once
    assert c0["busy_s"] == pytest.approx(0.007)
    assert c1["busy_s"] == pytest.approx(0.005)
    assert red["busy_s"] == pytest.approx(0.006)       # mean over chips
    assert c0["collective_s"] == pytest.approx(0.001)
    assert c0["by_name"]["fusion.1"] == pytest.approx(0.004)
    assert xplane.time_matching(red, "ragged_paged_attention") \
        == pytest.approx(0.001)
    assert xplane.time_matching(red, r"^fusion", chip=1) \
        == pytest.approx(0.005)
    # the gap 3-5 ms is charged to the innermost span open in its middle
    # (admit_pending at 4 ms), the gap 9-10 ms to no span of ours
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["admit_pending"] == pytest.approx(0.002)
    assert gaps["no span"] == pytest.approx(0.001)
    # the breakdown groups fusion.1, fusion.2, ... into one family
    assert red["device_ops"][0] == ["fusion", pytest.approx(0.004)]
    assert len(red["device_ops"]) <= 10


def test_event_names_are_cut_to_the_instruction_and_kernels_marked():
    line = ('%shard_map.7 = bf16[4,8]{1,0} custom-call(bf16[4,8]{1,0} %p), '
            'custom_call_target="tpu_custom_call"')
    assert xplane.short_name(line) == "shard_map.7 [tpu_custom_call]"
    assert xplane.short_name("%fusion.3 = f32[8]{0} fusion(...)") \
        == "fusion.3"
    assert xplane.short_name("decode_tick") == "decode_tick"
    assert xplane._by_family({"a.1 [tpu_custom_call]": 1.0,
                              "a.22 [tpu_custom_call]": 2.0, "b": 1.0}) \
        == {"a [tpu_custom_call]": 3.0, "b": 1.0}


def test_window_clips_events_and_short_gaps_are_the_chips_own():
    devices = {0: [("a", 0.0, 4 * MS), ("b", 4 * MS + 5_000, 2 * MS)]}
    red = xplane.reduce_events(devices, {}, span_names=(),
                               window=(2 * MS, 5 * MS))
    assert red["per_chip"][0]["by_name"]["a"] == pytest.approx(0.002)
    assert red["busy_s"] == pytest.approx(0.003 - 5e-6)
    assert dict(map(tuple, red["idle_gaps"])) == {
        "gaps under 20 us": pytest.approx(5e-6)}


def test_no_device_events_reduce_to_nothing():
    red = xplane.reduce_events({}, {"t": [("x", 0, 1)]})
    assert red["chips"] == 0 and red["busy_s"] == 0.0
    assert xplane.time_matching(red, "x") == 0.0


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "chat-steady.v5e.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_reduction_of_the_trace_recorded_on_the_chip():
    planes = xplane.read_planes(RECORDED)
    assert sorted(planes["devices"]) == [0]
    from benchmark.serving import TICK_SPANS

    red = xplane.reduce_events(planes["devices"], planes["host"],
                               span_names=TICK_SPANS)
    assert red["chips"] == 1
    assert 0.0 < red["busy_s"] < red["window_s"]
    attn = xplane.time_matching(red, "^ragged_paged_attention")
    assert 0.0 < attn < red["busy_s"]
    # every idle second is charged to something, and the spans of the
    # program's tick loop are found on the trace's own clock
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _n, s in red["idle_gaps"]) <= idle * 1.0001
    named = {n for n, _s in red["idle_gaps"]}
    assert named & set(TICK_SPANS)
