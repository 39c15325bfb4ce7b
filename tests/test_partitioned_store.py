"""The store channel in Spark's partitioned layout (the benchmark's cell
``store_sf10_tasks``: q3 and q55 over tables of ten partitions, run as
tasks), on the benchmark generator's tables at a twentieth of SF1, with
every table in 1, 4 and 10 partitions (the star texts q7_qual and q27_qual
in the same layouts: ``tests/test_partitioned_store_q7.py``, ``..._q27.py``).

What the cell needs of the engine is pinned here at a small size: a join's
small side is broadcast by Spark's size rule
(``spark.sql.autoBroadcastJoinThreshold``, ``plan/join_selection.py``) and
nothing but the partial aggregate crosses an exchange; every layout gives
the plain numpy reference's answer and, row for row, the one-partition
answer, with the rule on and with every join shuffled; a broadcast side is
built once a query whatever the number of probe tasks; the exchange, the
broadcast build and the task runner are under spans, and the summary's
counters add up over sibling tasks.
"""

import os
import sys

import pytest

from spark_rapids_tpu.aux import tracing
from tests.partitioned_store import (LAYOUTS, THRESHOLD, answered,
                                     assert_reference_answer,
                                     assert_shuffled_same_rows,
                                     parents_plans, same_rows, sections,
                                     subtree)

TEXTS = ("q3", "q55")



@pytest.fixture(scope="module")
def store():
    """The cell's two texts answered in every layout by Spark's rule and,
    in four partitions, with every join shuffled; the ten-partition
    session by the rule comes last and stays warm."""
    got = answered(TEXTS, [(1, "rule"), (4, "rule"), (4, "shuffled"),
                           (10, "rule")])
    yield got
    for s in got["sessions"].values():
        s.stop()


@pytest.mark.parametrize("q", TEXTS)
@pytest.mark.parametrize("n", LAYOUTS)
def test_every_layout_gives_the_reference_answer(store, n, q):
    assert_reference_answer(store, n, q)


@pytest.mark.parametrize("q", TEXTS)
def test_every_join_shuffled_gives_the_same_rows(store, q):
    """With the threshold at -1 no join is broadcast: the shuffled path
    keeps a differential test now that every small table plans as a
    broadcast."""
    assert_shuffled_same_rows(store, 4, q)


@pytest.mark.parametrize("q", ("q3", "q55"))
def test_ten_partitions_plan_two_broadcast_joins_and_one_hash_exchange(
        store, q):
    plan = sections(store["runs"][10, "rule", q]["explain"])["TPU Plan"]
    joins = [i for i, line in enumerate(plan) if "BroadcastHashJoin" in line]
    assert len(joins) == 2, "\n".join(plan)
    for i in joins:
        assert not any("Exchange" in line for line in subtree(plan, i))
    hashed = [i for i, line in enumerate(plan)
              if "Exchange[HashPartitioning(" in line]
    assert len(hashed) == 1, "\n".join(plan)
    # it is the one between the partial and the final aggregate
    assert "mode=partial" in plan[hashed[0] + 1]
    assert not any("ShuffledHashJoin" in line or "SubPartitionHashJoin" in
                   line for line in plan)


def test_one_partition_plans_are_byte_for_byte_the_parents(store):
    """The rule plans nothing new where both sides have one partition:
    the cells the benchmark had plan exactly as at the parent of the PR
    that added the rule."""
    for q in TEXTS:
        assert store["runs"][1, "rule", q]["explain"] == parents_plans()[q]


@pytest.mark.parametrize("n", LAYOUTS)
def test_a_broadcast_side_is_built_once_a_query(store, n):
    """Two joins, two builds, whatever the number of probe tasks (none at
    one partition, where nothing is broadcast)."""
    summary = store["runs"][n, "rule", "q3"]["summary"]
    assert summary["broadcast_builds"] == (0 if n == 1 else 2)
    if n > 1:
        kinds = summary["dispatches_by_kind"]
        # the first call of a program is its compile, not a dispatch
        assert kinds.get("join.build", 0) <= 2, kinds
        assert "broadcast.build" in summary["phases"]


def test_sibling_tasks_build_each_hash_table_once(store, monkeypatch):
    """Ten probe tasks on four threads meet one build: the tasks that
    arrive together wait for it and do not each build their own."""
    from spark_rapids_tpu.ops import join_ops as J
    calls = []
    real = J.build_side
    monkeypatch.setattr(J, "build_side",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rows = store["sessions"][10, "rule"].sql(
            store["texts"]["q3"]).collect()
    finally:
        sys.setswitchinterval(old_interval)
    assert len(calls) == 2
    assert same_rows(rows, store["runs"][1, "rule", "q3"]["rows"])
    assert tracing.last_query_summary()["broadcast_builds"] == 2


@pytest.mark.parametrize("n, mode", [(10, "rule"), (4, "rule"),
                                     (4, "shuffled")])
def test_the_exchange_counters_add_up(store, n, mode):
    """Rows written = rows read = the map side's live rows, over every
    exchange of the query and all of its sibling tasks.  (q55: a q3's
    range exchange samples its child for the bounds, which reads the
    aggregate's exchange a second time.)"""
    summary = store["runs"][n, mode, "q55"]["summary"]
    exchanges = [x for x in summary["nodes"] if "Exchange" in x["node"]]
    assert summary["exchanges"] == len(exchanges) > 0
    by_id = {x["span_id"]: x for x in summary["nodes"]}
    read = sum(x["numOutputRows"] for x in exchanges)
    children = [x for x in summary["nodes"]
                if by_id.get(x["parent_id"]) in exchanges]
    assert len(children) == len(exchanges)
    assert summary["exchange_rows"] == read > 0
    assert summary["exchange_rows"] == sum(x["numOutputRows"]
                                           for x in children)
    # a device piece is stored at a bucket, never under its live rows, and
    # a read writes a bucket, never under the rows it holds
    assert summary["exchange_rows_padded"] >= summary["exchange_rows"]
    assert summary["exchange_read_rows_padded"] >= summary["exchange_rows"]
    assert summary["exchange_host_staged_bytes"] == 0
    # a map batch stores ONE piece, its rows ordered by reduce partition:
    # n map tasks under the aggregate's exchange, fewer under one whose
    # child the adaptive reader coalesced
    assert len(exchanges) <= summary["exchange_pieces"] \
        <= n * len(exchanges)
    if mode == "rule":
        assert summary["exchange_pieces"] == n
    kinds = summary["dispatches_by_kind"]
    # (the first call of a program is its compile, not a dispatch)
    assert 0 < kinds["exchange.sort"] <= summary["exchange_pieces"]
    assert kinds["exchange.sort"] <= kinds["exchange.pid"] \
        <= n * len(exchanges)
    assert "exchange.split" not in kinds and "batch.compact" not in kinds
    # a read hands on one batch, so the coalescer above it has nothing to
    # concatenate: no ``batch.concat`` is dispatched for an exchange (what
    # is left are the broadcast builds' pulls, one a join at most)
    for x in exchanges:
        assert x["numOutputBatches"] == len(x["partitions"])
        above = by_id[x["parent_id"]]
        while "Coalesce" not in above["node"]:
            above = by_id[above["parent_id"]]
        assert above["numOutputBatches"] == x["numOutputBatches"]
    assert kinds.get("batch.concat", 0) <= summary["broadcast_builds"]


@pytest.mark.parametrize("n", LAYOUTS[1:])
def test_tasks_are_what_the_plans_stages_hold(store, n):
    """q55 at n partitions: n map tasks under the aggregate's exchange
    (scan, two probes, partial aggregate), and the reduce partitions the
    adaptive reader left of it under the root."""
    run = store["runs"][n, "rule", "q55"]
    summary = run["summary"]
    top = summary["nodes"][0]
    assert summary["tasks"] == n + len(top["partitions"])
    for name in ("task.run", "exchange.write", "exchange.read"):
        assert name in summary["phases"], sorted(summary["phases"])


def test_the_spans_are_opened_through_tracing_span_only():
    """The exchange, the broadcast build and the task runner write their
    spans with ``aux/tracing.py``'s primitives and nothing beside them."""
    import spark_rapids_tpu
    root = os.path.dirname(spark_rapids_tpu.__file__)
    for path in ("exec/exchange.py", "exec/joins.py", "plan/base.py",
                 "plan/join_selection.py"):
        with open(os.path.join(root, path)) as f:
            text = f.read()
        assert "TraceAnnotation" not in text and "jax.profiler" not in text


# ---------------------------------------------------------------------------
# the rule itself, on two small tables
# ---------------------------------------------------------------------------

def _two_tables(s, left_rows=4000, right_rows=300, n=4):
    import numpy as np
    rng = np.random.default_rng(5)
    left = {"k": rng.integers(0, 500, left_rows).astype("int64"),
            "v": rng.integers(0, 9, left_rows).astype("int64")}
    right = {"k": np.arange(right_rows, dtype="int64"),
             "w": rng.integers(0, 9, right_rows).astype("int64")}
    s.create_or_replace_temp_view(
        "l", s.create_dataframe(left, num_partitions=n))
    s.create_or_replace_temp_view(
        "r", s.create_dataframe(right, num_partitions=n))


def _session(threshold=None):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.session import TpuSession
    conf = {"spark.rapids.sql.enabled": "true"}
    if threshold is not None:
        conf[THRESHOLD] = str(threshold)
    return TpuSession(TpuConf(conf))


def _input_plan(df):
    return sections(df.explain())["Physical Plan (input)"]


@pytest.mark.parametrize("small", ("right", "left"))
@pytest.mark.parametrize("over", (False, True))
def test_a_side_at_the_threshold_is_broadcast_one_byte_over_is_not(small,
                                                                   over):
    """The small table holds two int64 columns of 300 rows: 4,800 bytes."""
    from spark_rapids_tpu.plan.join_selection import estimated_bytes
    s = _session(4800 - 1 if over else 4800)
    try:
        _two_tables(s)
        assert estimated_bytes(s.catalog_lookup("r")._plan) == 4800
        assert estimated_bytes(s.catalog_lookup("l")._plan) == 64000
        sides = "r, l" if small == "left" else "l, r"
        first, second = sides.split(", ")
        df = s.sql(f"select {first}.k, v, w from {sides} "
                   f"where {first}.k = {second}.k")
        plan = _input_plan(df)
        if over:
            assert not any("Broadcast" in line for line in plan)
            assert sum("Exchange[HashPartitioning(" in line
                       for line in plan) == 2
        else:
            at = [i for i, line in enumerate(plan)
                  if "BroadcastHashJoin" in line]
            assert len(at) == 1 and not any(
                "Exchange" in line for line in subtree(plan, at[0]))
            # the build side is the broadcast join's second child: the
            # small table whichever side of the text it stands on, and a
            # projection restores the text's order where they were swapped
            scans = [line for line in subtree(plan, at[0])
                     if "InMemoryScan" in line]
            assert len(scans) == 2
            assert sum("Project[" in line for line in plan[:at[0]]) \
                == (2 if small == "left" else 1)
        rows = sorted(map(str, df.collect()))
        shuffled = _session(-1)
        try:
            _two_tables(shuffled)
            assert rows == sorted(map(str, shuffled.sql(
                f"select {first}.k, v, w from {sides} "
                f"where {first}.k = {second}.k").collect()))
        finally:
            shuffled.stop()
    finally:
        s.stop()


@pytest.mark.parametrize("how, builds", [
    ("inner", "either"), ("left", "right"), ("left_semi", "right"),
    ("left_anti", "right"), ("right", "left"), ("full", None)])
@pytest.mark.parametrize("small", ("right", "left"))
def test_the_build_side_follows_sparks_table(how, builds, small):
    """Left outer, semi and anti joins broadcast only their right side, a
    right outer join only its left, a full outer join neither, an inner
    join whichever is small; and the answer is the shuffled plan's either
    way.  The threshold stands between the two tables' sizes (4,800 and
    64,000 bytes)."""
    s, shuffled = _session(10000), _session(-1)
    try:
        for sess in (s, shuffled):
            _two_tables(sess)
        a, b = ("l", "r") if small == "right" else ("r", "l")

        def joined(sess):
            return sess.catalog_lookup(a).join(sess.catalog_lookup(b),
                                               on="k", how=how)
        df = joined(s)
        plan = _input_plan(df)
        assert any("BroadcastHashJoin" in line for line in plan) \
            == (builds in (small, "either")), "\n".join(plan)
        assert sorted(map(str, df.collect())) \
            == sorted(map(str, joined(shuffled).collect()))
    finally:
        s.stop()
        shuffled.stop()


def test_a_join_an_aggregate_or_a_union_has_no_estimate():
    from spark_rapids_tpu.plan.join_selection import estimated_bytes
    s = _session()
    try:
        _two_tables(s)
        assert estimated_bytes(s.sql("select k, sum(w) from r group by k")
                               ._plan) is None
        assert estimated_bytes(s.sql("select k from r union all "
                                     "select k from l")._plan) is None
        assert estimated_bytes(s.sql("select l.k from l, r where l.k = r.k")
                               ._plan) is None
        # a filter carries the estimate unchanged; a projection scales it
        # by the row's width
        assert estimated_bytes(s.sql("select * from r where w > 3")
                               ._plan) == 4800
        assert estimated_bytes(s.sql("select k from r where w > 3")
                               ._plan) == 2400
        assert estimated_bytes(s.sql("select k, w, k + w as kw from r")
                               ._plan) == 7200
    finally:
        s.stop()


def test_the_dataframe_join_plans_by_the_same_rule():
    """``DataFrame.join`` and the SQL text go through one helper: the
    small right side is broadcast without a hint, nothing is with the
    threshold at -1, and the hint keeps its meaning there."""
    from spark_rapids_tpu import functions as F
    for threshold, hint, expect in ((None, False, True), (-1, False, False),
                                    (-1, True, True)):
        s = _session(threshold)
        try:
            _two_tables(s)
            right = s.catalog_lookup("r")
            if hint:
                right = F.broadcast(right)
            df = s.catalog_lookup("l").join(right, on="k")
            assert any("BroadcastHashJoin" in line
                       for line in _input_plan(df)) == expect
        finally:
            s.stop()


@pytest.mark.parametrize("n", (1, 4))
def test_one_name_at_two_ordinals_does_not_share_a_program(n):
    """``select k, v, w`` over l joined to r reads other ordinals than over
    r joined to l, at one shape and under one text: a program cache keyed
    by the expressions' text alone handed the second query the first's
    program, and with it v for w (found with the swapped build side;
    ``expressions/base.py`` ``expr_key``)."""
    s, cpu = _session(-1), _session(-1)
    cpu.set_conf("spark.rapids.sql.enabled", "false")
    try:
        for sess in (s, cpu):
            _two_tables(sess, n=n)
        for text in ("select l.k, v, w from l, r where l.k = r.k",
                     "select r.k, v, w from r, l where r.k = l.k"):
            assert sorted(map(str, s.sql(text).collect())) \
                == sorted(map(str, cpu.sql(text).collect())), text
    finally:
        s.stop()
        cpu.stop()
