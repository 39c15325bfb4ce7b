"""The reader ``probe_gather_rounds_per_query``: the mean of the window's
summaries' ``probe_gather_rounds`` (the gathers a probe row of a hash
join makes to find its candidates: 2 a probed batch with the build
side's bucket-start table, 32 to 44 with two binary searches), on
hand-made summaries, silent where the program does not count it, and in
the last line of a traced rehearsal of a cell."""

import json
import os

import pytest

from conftest import ROOT

from test_span_metrics import read

NAME = "probe_gather_rounds_per_query"


def test_the_benchmark_lists_the_metric_for_both_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "operators exec/ and ops/", "moves": "qps",
                     "workloads": ["store_scan_agg", "store_star_join"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def test_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [{"probe_gather_rounds": 70}, {"probe_gather_rounds": 4},
            {"probe_gather_rounds": 8}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    # the newest two are the window's
    assert read(NAME) == 6.0
    assert isinstance(read(NAME), float)
    assert read(NAME, records=3) == pytest.approx(82 / 3)
    # fewer summaries than queries: silence
    assert read(NAME, records=4) is None
    # a query without a hash join counts 0
    held[2] = {"probe_gather_rounds": 0}
    assert read(NAME) == 2.0


def test_a_program_without_the_counter_is_silence(monkeypatch):
    """The parent of the PR that added the counter: its summaries hold
    the other counters and not this one."""
    from spark_rapids_tpu.aux import tracing
    held = [{"pair_rows_padded": 8388608, "expand_rows_padded": 0,
             "speculation_replays": 0}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(NAME) is None
    monkeypatch.setattr(tracing, "recent_summaries", lambda: [])
    assert read(NAME) is None


def test_a_traced_rehearsal_prints_two_gathers_a_join():
    from test_star_join import rehearse
    line, err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    said = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"cell"')))
    assert [q for q, _ in said["latency_s_in_order"]][:2] == \
        ["q27_qual", "q7_qual"]
    # four hash joins a q27 and a q7, one probe batch each
    assert line["rehearsal_metrics"][NAME]["value"] == 8.0
