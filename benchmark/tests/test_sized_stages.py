"""The reader ``sized_stages_per_query``: the mean of the window's
summaries' ``sized_stages`` (the batches of fused stages whose compact
terminal was sized by the filter's fetched live count), on recorded
summaries, and silent where the program does not count it."""

import json
import os

import pytest

from conftest import ROOT

from test_span_metrics import read

NAME = "sized_stages_per_query"


def test_the_benchmark_lists_the_metric_for_the_cells_that_report_qps():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    listed = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "operators exec/ and ops/", "moves": "qps"}
    cells = [w["name"] for w in bench["workloads"]]
    assert listed and set(listed) <= set(cells)
    assert {"store_scan_agg", "store_star_join",
            "served_streams"} <= set(listed)
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def test_on_recorded_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    # a star query sizes the demographic stage; the first is set-up's
    held = [{"sized_joins": 2, "sized_stages": 5},
            {"sized_joins": 2, "sized_stages": 1},
            {"sized_joins": 2, "sized_stages": 2}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    # the newest two are the window's
    assert read(NAME) == 1.5
    assert isinstance(read(NAME), float)
    assert read(NAME, records=3) == pytest.approx(8 / 3)
    # fewer summaries than queries: silence
    assert read(NAME, records=4) is None
    # a query whose stages all stay under the floor counts 0
    held[2] = {"sized_joins": 2, "sized_stages": 0}
    assert read(NAME) == 0.5


def test_a_program_without_the_counter_is_silence(monkeypatch):
    """The parent of the PR that added the counter: its summaries hold
    the other counters and not this one."""
    from spark_rapids_tpu.aux import tracing
    held = [{"pair_rows_padded": 8388608, "expand_rows_padded": 0,
             "probe_gather_rounds": 4, "speculation_replays": 0,
             "sized_joins": 2}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(NAME) is None
    monkeypatch.setattr(tracing, "recent_summaries", lambda: [])
    assert read(NAME) is None
