"""The readers of the gateway's device-gap ledger
(``layer_metrics/fetch_gap_ms_per_query.py``,
``layer_metrics/query_gap_ms_per_query.py``): the means of the window's
summaries' ``device_gaps``, silent where a summary has none (the parent of
the PR that added the ledger) or the program holds fewer summaries than
the window ran queries, and listed for every cell."""

import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the checkout on sys.path

from benchmark import run as R

GAP_METRICS = ("fetch_gap_ms_per_query", "query_gap_ms_per_query")


def summary(fetch_s, query_s):
    return {"duration_s": 1.0, "phases": {},
            "device_gaps": {"fetch_s": fetch_s, "query_s": query_s,
                            "count": 2, "late": 0, "unplaced": 0,
                            "by_span": {}, "by_site": {}, "intervals": [],
                            "intervals_dropped": 0}}


#: a warm lap the window does not own, then two queries by hand
HELD = [summary(9.0, 9.0), summary(0.004, 0.010), summary(0.006, 0.020)]


def read(name, held, monkeypatch, records=2):
    from spark_rapids_tpu.aux import tracing
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    run = types.SimpleNamespace(records=[{}] * records, completed=[],
                                trace=None)
    return R.load_by_name("layer_metrics", name).read(run)


def test_the_means_of_hand_made_summaries(monkeypatch):
    assert read("fetch_gap_ms_per_query", HELD, monkeypatch) == \
        pytest.approx((4.0 + 6.0) / 2)
    assert read("query_gap_ms_per_query", HELD, monkeypatch) == \
        pytest.approx((10.0 + 20.0) / 2)


@pytest.mark.parametrize("name", GAP_METRICS)
def test_silent_without_the_ledger(name, monkeypatch):
    parent = [{k: v for k, v in s.items() if k != "device_gaps"}
              for s in HELD]
    assert read(name, parent, monkeypatch) is None


@pytest.mark.parametrize("name", GAP_METRICS)
def test_silent_where_the_program_holds_fewer_summaries(name, monkeypatch):
    assert read(name, HELD, monkeypatch, records=4) is None


@pytest.mark.parametrize("name", GAP_METRICS)
def test_listed_for_every_cell_as_a_device_metric(name):
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    entry = entry[0]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"]) \
        == ("program_span", "device TPU v5e", "qps", "ms")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
