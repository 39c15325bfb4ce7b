"""The reduction from a trace to busy time, idle share and gap attribution:
on hand-made intervals, and on a small trace recorded on the chip."""

import json
import os

import pytest

from benchmark.trace.reduce import reduce_events, union

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "..", "trace", "fixture_trace.json")


def test_union_merges_overlaps_and_keeps_gaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def hand_made():
    s = 1e9
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1", 1 * s, 2 * s), ("sort.2", 2 * s, 2 * s),
                    ("fusion.1", 7 * s, 1 * s)],
            "modules": [("jit_scan", 1 * s, 3 * s), ("jit_agg", 7 * s, 1 * s)]}},
        "spans": [("plan", 0 * s, 1 * s), ("collect", 1 * s, 5.5 * s),
                  ("draw_literals", 6.5 * s, 0.5 * s), ("collect", 7 * s, 3 * s)],
    }


def test_busy_is_the_union_and_the_window_is_the_spans():
    got = reduce_events(hand_made())
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(4.0)      # [1,4] and [7,8]
    assert 100 * (1 - got["busy_s"] / got["window_s"]) == pytest.approx(60.0)
    # sort.2 starts inside fusion.1: the shared second is listed once
    assert got["device_ops"][0] == ["jit_scan:sort.2", pytest.approx(2.0)]
    assert dict(got["device_ops"])["jit_scan:fusion.1"] == pytest.approx(1.0)
    assert dict(got["device_ops"])["jit_agg:fusion.1"] == pytest.approx(1.0)
    assert got["programs"][0] == ["jit_scan", pytest.approx(3.0)]


def test_a_loop_is_listed_for_what_it_spends_outside_its_body():
    from benchmark.trace.reduce import self_times
    s = 1e9
    ops = [("while", 0, 10 * s), ("fusion.a", 1 * s, 3 * s),
           ("fusion.b", 4 * s, 5 * s), ("inner", 5 * s, 1 * s),
           ("after", 10 * s, 2 * s)]
    assert dict(self_times(ops)) == {"while": 2 * s, "fusion.a": 3 * s,
                                     "fusion.b": 4 * s, "inner": 1 * s,
                                     "after": 2 * s}


def test_programs_and_operations_get_short_names():
    from benchmark.trace.reduce import short_op, short_program
    assert short_program("jit_run_join.pair(16135105323470079009)") == "join.pair"
    assert short_op("%while.7 = (u32[]{:T(128)}) while(...)") == "%while.7"


def test_each_gap_goes_to_the_span_that_covers_most_of_it():
    gaps = dict(reduce_events(hand_made())["idle_gaps"])
    # [0,1] under plan; [4,7]: collect covers 2.5 s of it, draw_literals 0.5;
    # [8,10] under the second collect
    assert gaps == {"plan": pytest.approx(1.0),
                    "collect": pytest.approx(5.0)}
    assert sum(gaps.values()) == pytest.approx(10.0 - 4.0)


def test_a_trace_without_a_device_has_no_busy_time():
    events = {"devices": {}, "spans": [("collect", 0.0, 2e9)]}
    got = reduce_events(events)
    assert got["busy_s"] == 0.0 and got["window_s"] == pytest.approx(2.0)
    assert got["idle_gaps"] == [["collect", pytest.approx(2.0)]]


def test_several_devices_are_averaged():
    events = hand_made()
    events["devices"]["/device:TPU:1"] = {
        "ops": [("fusion.1", 1e9, 2e9)], "modules": []}
    assert reduce_events(events)["busy_s"] == pytest.approx((4.0 + 2.0) / 2)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_the_recorded_trace_reduces_to_the_values_worked_from_it():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    events = {"devices": {k: {"ops": [tuple(e) for e in v["ops"]],
                              "modules": [tuple(e) for e in v["modules"]]}
                          for k, v in fixture["devices"].items()},
              "spans": [tuple(e) for e in fixture["spans"]]}
    got = reduce_events(events)
    want = fixture["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    # self times list no nanosecond twice: they add up to the busy time
    assert sum(t for _, t in got["device_ops"]) == pytest.approx(
        want["busy_s"], rel=1e-6)
    # each operation's self time, worked out here by comparing every pair:
    # its duration less the operations it directly holds
    from benchmark.trace.reduce import short_op
    ops = next(iter(events["devices"].values()))["ops"]
    inside = lambda a, b: b[1] <= a[1] and a[1] + a[2] <= b[1] + b[2] \
        and a is not b
    want_own, got_own = {}, {}
    for op in ops:
        kids = [k for k in ops if inside(k, op)]
        direct = [k for k in kids if not any(inside(k, j) for j in kids)]
        want_own[short_op(op[0])] = want_own.get(short_op(op[0]), 0.0) \
            + (op[2] - sum(k[2] for k in direct)) / 1e9
    for name, t in got["device_ops"]:
        op = name.split(":", 1)[1]
        got_own[op] = got_own.get(op, 0.0) + t
    assert got_own == pytest.approx(want_own, rel=1e-6, abs=1e-9)
    assert got["programs"][0][0] == want["top_program"]
    assert dict(got["idle_gaps"]).keys() == set(want["gap_spans"])
