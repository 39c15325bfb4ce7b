"""The star-join class of the TPC-DS store channel (the benchmark's q7 and
q27: four joins with a demographic dimension filtered by three string
parameters; q27 groups by ROLLUP) through ``TpuSession.sql``, on the
benchmark generator's tables at a twentieth of SF1, against the benchmark's
plain numpy references: exact rows, doubles to 1e-9.

What the cell ``store_star_join`` needs of the engine is pinned here at a
small size: the roll-up's fan-out runs at the size of the join chain's live
rows and not at three padded copies of its output, the fan-out is counted,
and a new GEN/MS/ES triple traces no program.
"""

import pytest

from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.columnar.column import SIZED_MIN_BUCKET
from spark_rapids_tpu.exec import stage_compiler as SC

SEED = 2147493319
#: store_sales 144,020 rows: the first join probes with a 262,144-row
#: bucket, well above ``SIZED_MIN_BUCKET``, of which a q27 keeps some
#: 200 rows
SCALE_DOWN = 20
PARAMS = {
    "q7": [{"GEN": "M", "MS": "S", "ES": "College", "YEAR": 2000},
           {"GEN": "F", "MS": "W", "ES": "Advanced Degree", "YEAR": 1999}],
    "q27": [{"GEN": "F", "MS": "D", "ES": "Primary", "YEAR": 2001,
             "STATE": "TN"},
            {"GEN": "M", "MS": "U", "ES": "2 yr Degree", "YEAR": 1998,
             "STATE": "TN"}],
}


@pytest.fixture(scope="module")
def star():
    """The cell as the benchmark builds it, a session over its tables, and
    each text answered with both of its parameter draws (the first draw
    builds the programs)."""
    from benchmark import run as bench
    from benchmark.literals import Query
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.session import TpuSession
    # the cell's own texts are these templates with the strings fixed
    # (``q7_qual``, ``q27_qual``): the tables are the same
    cell = bench.Cell("store_star_join", SCALE_DOWN)
    gen, tables = bench.make_tables(cell, SEED)
    session = TpuSession(TpuConf(dict(cell.config["session_conf"])))
    for name, table in tables.items():
        session.create_or_replace_temp_view(
            name, session.create_dataframe(
                table, num_partitions=int(cell.config["partitions"])))
    runs = []
    for q, draws in PARAMS.items():
        for nth, params in enumerate(draws):
            traces = SC.stats()["traces"]
            rows = session.sql(Query(q).fill(params)).collect()
            runs.append({"q": q, "nth": nth, "params": params, "rows": rows,
                         "summary": tracing.last_query_summary(),
                         "traces": SC.stats()["traces"] - traces})
    yield cell, gen, runs
    session.stop()


@pytest.mark.parametrize("q,nth", [("q7", 0), ("q7", 1), ("q27", 0),
                                   ("q27", 1)])
def test_answers_match_the_plain_reference(star, q, nth):
    from benchmark import run as bench
    from benchmark.compare import compare
    cell, gen, runs = star
    run = next(r for r in runs if r["q"] == q and r["nth"] == nth)
    answer = bench.load_by_name("reference", q).run(gen, run["params"])
    got = compare(run["rows"], answer)
    assert got["groups"] > 0, "the draw keeps no row: nothing was compared"
    assert got["rows_wrong"] == 0, got
    assert got["max_rel_err"] <= 1e-9, got
    if q == "q27":
        # the roll-up's three levels are all among the first hundred rows:
        # the grand total (nulls first), an item's total, an item by state
        levels = {(r["i_item_id"] is None, r["s_state"] is None,
                   r["g_state"]) for r in run["rows"]}
        assert levels == {(True, True, 1), (False, True, 1),
                          (False, False, 0)}


def test_the_fan_out_runs_at_the_live_rows_size(star):
    """q27's fan-out hands the aggregation three buckets of the join
    chain's live rows, where three padded copies of the fact table's
    bucket were handed before: the first join, whose probe is the fact
    table's 262,144-row bucket, is sized by its candidate total and hands
    on the floor's bucket, and everything above it runs at that."""
    _, _, runs = star
    for run in (r for r in runs if r["q"] == "q27"):
        s = run["summary"]
        joins = [n for n in s["nodes"] if "HashJoin" in n["node"]]
        assert len(joins) == 4
        for join in joins:
            assert sum(p["padded_rows"] for p in join["partitions"]) \
                == SIZED_MIN_BUCKET
        scan = max(sum(p["padded_rows"] for p in n["partitions"])
                   for n in s["nodes"] if "Scan" in n["node"])
        assert scan > SIZED_MIN_BUCKET
        assert s["expand_rows_padded"] == 3 * SIZED_MIN_BUCKET
        assert 0 < s["expand_rows_padded"] <= scan
        expand = next(n for n in s["nodes"] if "Expand" in n["node"])
        assert sum(p["padded_rows"] for p in expand["partitions"]) \
            == s["expand_rows_padded"]


@pytest.mark.parametrize("q", ["q7", "q27"])
def test_one_sized_join_a_query_and_its_syncs(star, q):
    """At a twentieth of SF1 the first join keeps some 2,000 rows: it is
    the one probe over the floor (one fetch, site ``join-size``), the
    other three probe at the floor's bucket, and the fan-out forces no
    count.  Every join is a fact-to-dimension join on a unique key: four
    direct tables, each built with one fetch (site ``join-build``), so
    the three small probes' pair tables are their buckets and flag no
    overflow for the collect to check.  Two filtered dimensions are over
    the floor, ``customer_demographics`` (96,040 rows) and ``date_dim``
    (73,049 at every scale), both in a 131,072-row bucket: each stage
    fetches its live count (site ``stage-size``) and hands the join a
    build side at the floor's bucket."""
    _, _, runs = star
    for run in (r for r in runs if r["q"] == q):
        s = run["summary"]
        assert s["sized_joins"] == 1
        assert s["direct_joins"] == 4
        assert s["probe_gather_rounds"] == 4
        assert s["pair_rows_padded"] == (1 + 3) * SIZED_MIN_BUCKET
        assert s["speculation_replays"] == 0
        assert s["sized_stages"] == 2
        assert s["transitions"]["sync_count"] == 1 + 4 + 2


def test_a_query_without_grouping_sets_counts_no_fan_out(star):
    _, _, runs = star
    for run in (r for r in runs if r["q"] == "q7"):
        assert run["summary"]["expand_rows_padded"] == 0


def test_new_string_parameters_trace_nothing(star):
    """The second draw of each text differs in all three string
    parameters and in the year, and builds no program."""
    _, _, runs = star
    assert [r["traces"] for r in runs if r["nth"] == 1] == [0, 0]
    assert all(r["traces"] > 0 for r in runs if r["nth"] == 0)


def test_the_fan_out_programs_have_a_kind_of_their_own(star):
    assert SC.stats()["traces_by_kind"].get("expand.project", 0) >= 3
