"""The reader ``join_cols_pruned_per_query``: the mean of the window's
summaries' ``join_cols_pruned`` (for each output batch of a device join,
the columns its children delivered that it did not gather), on hand-made
summaries, silent where the program does not count it, listed for every
cell, and in the last line of a traced rehearsal."""

import json
import os

import pytest

from conftest import ROOT

from test_span_metrics import read

NAME = "join_cols_pruned_per_query"


def test_the_benchmark_lists_the_metric_for_every_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter",
                     "layer": "operators exec/ and ops/", "moves": "qps",
                     "workloads": [w["name"] for w in bench["workloads"]]}
    assert bench["per_layer"][-1] == entry


def test_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [{"join_cols_pruned": 9}, {"join_cols_pruned": 6},
            {"join_cols_pruned": 7}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    # the newest two are the window's
    assert read(NAME) == 6.5
    assert read(NAME, records=3) == pytest.approx(22 / 3)
    assert read(NAME, records=4) is None


def test_a_program_without_the_counter_is_silence(monkeypatch):
    """The parent of the PR that added the counter."""
    from spark_rapids_tpu.aux import tracing
    held = [{"direct_joins": 2, "sized_joins": 0}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(NAME) is None


def test_a_traced_rehearsal_prints_the_columns_a_star_query_leaves():
    from test_star_join import rehearse
    line, err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    said = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"cell"')))
    texts = [q for q, _ in said["latency_s_in_order"]]
    # one probe batch a join: a q27's four joins leave 5 + 3 + 2 + 2
    # columns of their children, a q7's 5 + 3 + 2 + 4
    n27 = texts.count("q27_qual")
    want = (12 * n27 + 14 * (len(texts) - n27)) / len(texts)
    assert line["rehearsal_metrics"][NAME]["value"] == pytest.approx(want)
