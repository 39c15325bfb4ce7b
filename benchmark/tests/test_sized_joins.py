"""The reader ``sized_joins_per_query``: the mean of the window's
summaries' ``sized_joins`` (the probe batches of hash joins sized by the
probe's fetched candidate total: every one over the join's floor of 32,768
rows), on hand-made summaries, silent where the program does not count it,
and in the last line of a traced rehearsal of a cell."""

import json
import os

import pytest

from conftest import ROOT

from test_span_metrics import read

NAME = "sized_joins_per_query"


def test_the_benchmark_lists_the_metric_for_both_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "operators exec/ and ops/", "moves": "qps",
                     "workloads": ["store_scan_agg", "store_star_join"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def test_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [{"sized_joins": 9}, {"sized_joins": 2}, {"sized_joins": 3}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    # the newest two are the window's
    assert read(NAME) == 2.5
    assert isinstance(read(NAME), float)
    assert read(NAME, records=3) == pytest.approx(14 / 3)
    # fewer summaries than queries: silence
    assert read(NAME, records=4) is None
    # a query whose joins all speculate counts 0
    held[2] = {"sized_joins": 0}
    assert read(NAME) == 1.0


def test_a_program_without_the_counter_is_silence(monkeypatch):
    """The parent of the PR that added the counter: its summaries hold
    the other counters and not this one."""
    from spark_rapids_tpu.aux import tracing
    held = [{"pair_rows_padded": 8388608, "expand_rows_padded": 0,
             "probe_gather_rounds": 4, "speculation_replays": 0}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(NAME) is None
    monkeypatch.setattr(tracing, "recent_summaries", lambda: [])
    assert read(NAME) is None


def test_a_traced_rehearsal_prints_the_sized_joins_of_a_star_query():
    from test_star_join import rehearse
    line, err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    # at a twentieth of SF1 the fact table's bucket (262,144 rows) is the
    # one probe over the floor: the first join keeps some 2,000 rows, so
    # the other three probe at 32,768 and speculate
    assert line["rehearsal_metrics"][NAME]["value"] == 1.0
