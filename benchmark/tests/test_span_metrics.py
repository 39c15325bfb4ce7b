"""The per-layer readers of the program's own spans and counters
(``benchmark/spans.py``): each on a hand-made ``run`` and hand-made
summaries, silent where the program holds fewer summaries than the window
ran queries or publishes none of the kind (the parent of the PR that added
them), and all of them in the last line of a traced rehearsal."""

import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the checkout on sys.path

from benchmark import run as R
from test_run import rehearse

SPAN_METRICS = ("rewrite_ms", "host_unblocked_ms_per_query",
                "dispatches_per_query", "replays_per_query", "pad_factor")


def summary(rewrite_s, d2h_s, duration_s, dispatches, replays, pair_rows,
            nodes):
    return {"duration_s": duration_s,
            "phases": {"plan.parse": 0.001, "plan.rewrite": rewrite_s,
                       "exec.run": duration_s - rewrite_s - d2h_s - 0.002,
                       "xfer.d2h": d2h_s, "xfer.sync": 0.001,
                       "(unattributed)": 0.0},
            "dispatches": dispatches, "speculation_replays": replays,
            "pair_rows_padded": pair_rows, "nodes": nodes}


def node(device, rows, *padded):
    return {"node": "TpuX" if device else "CpuX", "device": device,
            "numOutputRows": rows,
            "partitions": [{"rows": 0, "batches": 1, "padded_rows": p}
                           for p in padded]}


#: two queries by hand.  q1: 4 ms rewrite, 10.0 s with 6.0 s in d2h and
#: 1 ms in a sync; q2 replayed: 6 ms over two rewrites, 20.0 s, 12.0 s
HELD = [
    summary(9.0, 9.0, 99.0, 1, 0, 0, []),       # the warm lap: not ours
    summary(0.004, 6.0, 10.0, 300, 0, 2048,
            [node(True, 1000, 1024, 1024), node(True, 24, 128),
             node(False, 5000, 9999)]),
    summary(0.006, 12.0, 20.0, 500, 1, 4096,
            [node(True, 1000, 1024), node(True, 0, 1024)]),
]


@pytest.fixture
def held(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    state = {"held": list(HELD)}
    monkeypatch.setattr(tracing, "recent_summaries",
                        lambda: list(state["held"]))
    return state


def read(name, records=2, trace=None):
    run = types.SimpleNamespace(records=[{}] * records, completed=[],
                                trace=trace)
    return R.load_by_name("layer_metrics", name).read(run)


def test_each_reader_on_hand_made_summaries(held):
    assert read("rewrite_ms") == pytest.approx((4.0 + 6.0) / 2)
    # 10.0 - 6.0 - 0.001 and 20.0 - 12.0 - 0.001 seconds
    assert read("host_unblocked_ms_per_query") == \
        pytest.approx((3999.0 + 7999.0) / 2)
    assert read("dispatches_per_query") == 400.0
    assert read("replays_per_query") == 0.5
    # device nodes only: buckets 1024+1024+128 + 1024+1024, pair tables
    # 2048 + 4096, over 1000 + 24 + 1000 + 0 live rows
    assert read("pad_factor") == pytest.approx(
        (4224 + 6144) / 2024)
    for name in SPAN_METRICS:
        assert isinstance(read(name), float)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_fewer_summaries_than_queries_is_silence(name, held):
    assert read(name, records=4) is None
    assert read(name, records=0) is None
    held["held"] = []
    assert read(name) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_spans_is_silence(name, held):
    """What the parent's summaries hold: nothing of this."""
    held["held"] = [{"duration_s": 1.0, "nodes": [
        {"node": "TpuX", "numOutputRows": 5,
         "partitions": [{"rows": 5, "batches": 1}]}]}] * 2
    assert read(name) is None


def test_pad_factor_without_a_live_row_is_silence(held):
    held["held"] = [summary(0.0, 0.0, 1.0, 1, 0, 64,
                            [node(True, 0, 64)])] * 2
    assert read("pad_factor") is None


def test_join_device_pct_on_a_hand_made_trace(held):
    trace = {"programs": [["join.pair", 6.0], ["join.probe", 2.0],
                          ["fused.agg_update", 1.5], ["sort.fused", 0.5]]}
    assert read("join_device_pct", trace=trace) == pytest.approx(80.0)
    assert isinstance(read("join_device_pct", trace=trace), float)
    # no device plane was traced (a rehearsal), or no trace was taken
    assert read("join_device_pct", trace={"programs": []}) is None
    assert read("join_device_pct", trace=None) is None
    assert read("join_device_pct", trace={"busy_s": 0.0}) is None


def test_a_traced_rehearsal_prints_the_five_that_need_no_device():
    line, _err = rehearse("store_scan_agg", 1)
    got = line["rehearsal_metrics"]
    assert set(SPAN_METRICS) <= set(got), sorted(got)
    assert "join_device_pct" not in got          # no device ran
    assert got["replays_per_query"]["value"] == 0.0
    assert got["dispatches_per_query"]["value"] > 0
    assert got["pad_factor"]["value"] >= 1.0
    assert got["rewrite_ms"]["value"] > 0
    # the rewrite is part of the host's own time
    assert got["rewrite_ms"]["value"] \
        < got["host_unblocked_ms_per_query"]["value"]
