"""The device shuffle's store (``exec/exchange.py``): ONE piece a map batch,
its rows ordered by reduce partition id, and a reduce read that gathers a
row range of each piece into one batch at the bucket of what it holds.

Driven at the exec level on a small table (keys with nulls, a double, a
string column; four map partitions of which one filters down to an empty
map batch; fewer keys than reduce partitions, so some are empty): every
read is compared with what numpy makes of the same map batches and the
same partition ids.
"""

import numpy as np
import pytest

from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.exec import exchange as X
from spark_rapids_tpu.exec import stage_compiler as SC
from spark_rapids_tpu.exec.adaptive import (AdaptiveShuffleReaderExec,
                                            PartialPartitionSpec,
                                            skew_split_specs)
from tests.asserts import tpu_session

ROWS = 6000
MAPS = 4
#: rows of the third map partition: the filter keeps none of them
EMPTY_MAP = range(2 * ROWS // MAPS, 3 * ROWS // MAPS)


def _table(seed=3, rows=ROWS):
    rng = np.random.default_rng(seed)
    k = [None if i % 11 == 0 else int(x)
         for i, x in enumerate(rng.integers(0, 5, rows))]
    keep = np.ones(rows, dtype=np.int64)
    keep[list(EMPTY_MAP)] = 0
    return {"k": k, "v": rng.normal(size=rows),
            "s": [f"r{i % 97}" * (1 + i % 3) for i in range(rows)],
            "keep": keep}


def _exchange(kind, table=None, n=8):
    """The device exchange of ``kind`` over the filtered table, found in
    the rewritten plan."""
    from spark_rapids_tpu.expressions.base import col, lit
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(table or _table(), num_partitions=MAPS)
    df = df.filter(col("keep") == lit(1)).select("k", "v", "s")
    df = {"hash": lambda: df.repartition(n, "k"),
          "hash_string": lambda: df.repartition(n, "s"),
          "round_robin": lambda: df.repartition(n),
          "range": lambda: df.order_by("k", "s")}[kind]()
    plan = TpuOverrides(s.conf).apply(df._plan)
    found = plan.collect_nodes(
        lambda x: isinstance(x, X.TpuShuffleExchangeExec) and
        x.num_partitions > 1)
    assert len(found) == 1, plan
    return found[0]


def _rows(batches):
    out = []
    for b in batches:
        hb = b.to_host() if hasattr(b, "bucket") else b
        d = hb.to_pydict()
        out.extend(zip(*(d[name] for name in d)))
    return out


def _expected(ex):
    """Every reduce partition's rows as numpy makes them: the map batches
    in map order, each row to the id the partitioning's CPU twin gives
    it."""
    from spark_rapids_tpu.plan.partitioning import RoundRobinPartitioning
    n = ex.num_partitions
    want = [[] for _ in range(n)]
    for mp in range(ex.child.num_partitions):
        part = ex.partitioning
        if isinstance(part, RoundRobinPartitioning):
            part = RoundRobinPartitioning(n, start=mp)
        for b in ex.child.execute_partition(mp):
            hb = b.to_host()
            rows = _rows([hb])
            for pid, row in zip(part.partition_ids_cpu(hb), rows):
                want[pid].append(row)
    return want


KINDS = ("hash", "hash_string", "round_robin", "range")


@pytest.fixture(scope="module", params=KINDS)
def stored(request):
    ex = _exchange(request.param)
    ex._materialize()
    singles = [_rows(ex.execute_partition(p))
               for p in range(ex.num_partitions)]
    if request.param == "hash":
        assert any(not rows for rows in singles), "no empty partition"
    return ex, singles


def test_a_partition_reads_its_rows_in_map_order(stored):
    ex, singles = stored
    want = _expected(ex)
    assert singles == want
    assert sum(map(len, singles)) == ROWS - len(EMPTY_MAP)


@pytest.mark.parametrize("start, end", [(0, 0), (1, -1), (2, 3), (-2, 0)])
def test_a_range_reads_as_the_single_reads_one_after_another(stored, start,
                                                             end):
    """Row for row and in order; one batch whatever the range holds, none
    where it holds nothing.  (The range exchange has four partitions, the
    others eight: the ends count from the last.)"""
    ex, singles = stored
    start, end = start % ex.num_partitions, end % ex.num_partitions or \
        ex.num_partitions
    got = list(ex.read_range(start, end))
    want = [row for rows in singles[start:end] for row in rows]
    assert _rows(got) == want
    assert len(got) == (1 if want else 0)
    if want:
        from spark_rapids_tpu.columnar.column import bucket_rows
        assert got[0].bucket == bucket_rows(len(want))
        assert int(got[0].row_count) == len(want)


def test_the_store_holds_one_piece_a_map_batch(stored):
    ex, singles = stored
    store = ex._store
    assert isinstance(store, X._SortedStore)
    assert len(store.pieces) == MAPS        # the empty map batch too
    assert [p.rows() for p in store.pieces].count(0) == 1
    assert all(p.on_device and p.counts is None for p in store.pieces)
    # the sizes the adaptive reader plans by are the live rows'
    sizes = ex.partition_sizes()
    assert [bool(x) for x in sizes] == [bool(rows) for rows in singles]
    assert ex.partition_sizes(1 << 40) == sizes


def test_a_skew_split_reads_the_partition_in_runs_of_pieces(stored):
    ex, singles = stored
    p = max(range(ex.num_partitions), key=lambda i: len(singles[i]))
    specs = skew_split_specs(ex, p, target_bytes=1)
    assert specs == [PartialPartitionSpec(p, i, i + 1) for i in range(MAPS)]
    sizes = ex.piece_sizes(p)
    assert len(sizes) == MAPS and sizes.count(0) == 1
    assert skew_split_specs(ex, p, sum(sizes)) \
        == [PartialPartitionSpec(p, 0, MAPS)]
    reader = AdaptiveShuffleReaderExec(ex, specs=specs)
    runs = [_rows(reader.execute_partition(i))
            for i in range(reader.num_partitions)]
    assert [row for run in runs for row in run] == singles[p]
    assert sum(1 for run in runs if not run) == 1   # the empty map batch
    assert _rows(ex.read_range(p, p + 1, (1, 4))) \
        == [row for run in runs[1:] for row in run]


def test_many_map_batches_are_read_in_groups(stored, monkeypatch):
    """More pieces than one program takes: groups of two are merged into
    pieces that are read again, and the rows come out as before."""
    ex, singles = stored
    monkeypatch.setattr(X, "_READ_GROUP", 2)
    before = SC.stats()["dispatches_by_kind"].get("exchange.read", 0) + \
        SC.stats()["traces_by_kind"].get("exchange.read", 0)
    assert _rows(ex.read_range(0, ex.num_partitions)) \
        == [row for rows in singles for row in rows]
    after = SC.stats()["dispatches_by_kind"].get("exchange.read", 0) + \
        SC.stats()["traces_by_kind"].get("exchange.read", 0)
    assert after - before == 3          # two groups of two, then the two


@pytest.mark.parametrize("kind", ("hash", "range"))
def test_a_store_that_turns_to_host_staging_reads_the_same_rows(
        kind, monkeypatch):
    """The free-HBM budget admits one map batch: the rest are kept on the
    host in the same layout, and a read hands on the device pieces' rows,
    then a slice of each staged piece."""
    whole = _exchange(kind)
    n = whole.num_partitions
    singles = [_rows(whole.execute_partition(p)) for p in range(n)]
    first = next(iter(whole._store.pieces)).batch.nbytes()
    monkeypatch.setattr(X.TpuShuffleExchangeExec, "_device_store_budget",
                        lambda self: first + 1)
    ex = _exchange(kind)
    ex._materialize()
    where = [p.on_device for p in ex._store.pieces]
    assert where.count(True) == 1 and where.count(False) == MAPS - 1
    for p in range(n):
        assert sorted(map(repr, _rows(ex.execute_partition(p)))) \
            == sorted(map(repr, singles[p]))
    assert sorted(map(repr, _rows(ex.read_range(0, n)))) \
        == sorted(repr(row) for rows in singles for row in rows)
    assert [bool(x) for x in ex.partition_sizes()] \
        == [bool(rows) for rows in singles]


def test_an_exchange_fetches_once_for_its_whole_map_side():
    ex = _exchange("hash")
    before = TR.totals()["sync_count"]
    ex._materialize()
    assert TR.totals()["sync_count"] - before == 1
    # nothing that follows needs another: sizes, reads, the map batches'
    # own row counts
    ex.partition_sizes()
    for p in range(8):
        list(ex.execute_partition(p))
    list(ex.read_range(0, 8))
    assert TR.totals()["sync_count"] - before == 1


def _grouped(s, table):
    from spark_rapids_tpu import functions as F
    return (s.create_dataframe(table, num_partitions=MAPS)
            .group_by("k", "s").agg(F.sum("v").alias("sv"))
            .order_by("k", "s"))


def test_other_data_at_the_same_shapes_builds_no_program():
    """Keys are shapes alone: other values, and other counts a partition
    within the same buckets, run the programs the first query built."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    try:
        first = _grouped(s, _table(seed=3)).collect()
        summary = tracing.last_query_summary()
        built = SC.stats()
        other = _table(seed=4)
        second = _grouped(s, other).collect()
        again = SC.stats()
        assert first != second
        for k in ("misses", "compiles", "traces"):
            assert again[k] == built[k], (k, built[k], again[k])
        kinds = tracing.last_query_summary()["dispatches_by_kind"]
        # a range exchange evaluates its keys under the same kind
        assert 0 < kinds["exchange.sort"] <= kinds["exchange.pid"]
        assert kinds["exchange.read"] > 0
        assert "exchange.split" not in kinds and "batch.concat" not in kinds
        assert summary["exchange_read_rows_padded"] \
            >= summary["exchange_rows"] > 0
    finally:
        s.stop()
