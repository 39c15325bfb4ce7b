"""A fused stage that filters sizes what it hands on by what it kept
(``exec/fused.py``): a batch whose bucket is over the floor leaves the
compact terminal at ``max(bucket_rows(count), floor)`` rows with a known
count (one fetch, site ``stage-size``, counted in ``sized_stages``; the
gathers are the program ``fused.compact``); a batch at or under the floor
keeps the one program and pays no fetch.  The floor (the joins' too:
``columnar/column.SIZED_MIN_BUCKET``) is patched down so a few thousand
rows are a large batch; every answer is the CPU engine's.
"""

import sys
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.columnar import column as COL
from spark_rapids_tpu.columnar.column import DeferredCount, bucket_rows
from spark_rapids_tpu.exec import fused
from spark_rapids_tpu.exec import stage_compiler as SC

from tests.asserts import _compare_rows, cpu_session, tpu_session

#: the patched floor, and a batch over it: 6,000 rows in an 8,192-row bucket
FLOOR = 2048
ROWS = 6000
BUCKET = 8192


@pytest.fixture
def patched_floor(monkeypatch):
    """Patches the floor.  Whether a stage is sized is a function of its
    batch's bucket and the floor, and the floor, a constant, is in no
    program's key: the programs cached under another floor are dropped
    here and again before the floor is put back."""
    def patch(value):
        monkeypatch.setattr(COL, "SIZED_MIN_BUCKET", value)
        SC.clear()
        return value

    yield patch
    SC.clear()


@pytest.fixture
def floor(patched_floor):
    return patched_floor(FLOOR)


@pytest.fixture
def handed_on(monkeypatch):
    """Every batch a fused stage hands on: its bucket, its row count as
    the stage left it, and its columns."""
    seen = []
    finish = fused.TpuFusedStageExec._finish

    def recording(self, *args, **kwargs):
        out = finish(self, *args, **kwargs)
        seen.append({"bucket": out.bucket, "row_count": out.row_count,
                     "columns": list(out.columns)})
        return out

    monkeypatch.setattr(fused.TpuFusedStageExec, "_finish", recording)
    return seen


@pytest.fixture
def syncs_by_site(monkeypatch):
    """The blocking syncs the gateway records, by site."""
    seen = {}
    record = TR._record_sync

    def counting(site, *args, **kwargs):
        seen[site] = seen.get(site, 0) + 1
        return record(site, *args, **kwargs)

    monkeypatch.setattr(TR, "_record_sync", counting)
    return seen


def _table(n=ROWS):
    rng = np.random.default_rng(36)
    words = ["", "a", "College", "Advanced Degree", "x" * 40, "é" * 9]
    return {
        "k": np.arange(n),
        "i": rng.integers(-5, 5, n),
        "d": rng.normal(size=n),
        "dn": [None if j % 11 == 0 else float(j) / 7 for j in range(n)],
        "s": [words[j % len(words)] for j in range(n)],
        "sn": [None if j % 5 == 0 else words[(j * 7) % len(words)]
               for j in range(n)],
    }


def _both(text, table, view="t"):
    """The text's rows from the CPU engine and from the TPU engine, and
    the TPU query's summary."""
    out = []
    for s in (cpu_session(), tpu_session()):
        try:
            s.create_or_replace_temp_view(view, s.create_dataframe(table))
            out.append(s.sql(text).collect())
        finally:
            s.stop()
    return out[0], out[1], tracing.last_query_summary()


COLUMNS = {"integer": "k, i", "double": "k, d", "null-bearing": "k, dn, sn",
           "strings-of-several-widths": "k, s, sn",
           "a-projection-after-the-filter": "k, i + 1 as j, d * 2 as e"}


@pytest.mark.parametrize("kind", list(COLUMNS))
def test_a_filter_over_the_floor_hands_on_the_bucket_of_what_it_kept(
        kind, floor, handed_on, syncs_by_site):
    # 2,500 rows kept of 6,000: the bucket of the count, over the floor
    cpu, tpu, summary = _both(
        f"select {COLUMNS[kind]} from t where k % 12 < 5", _table())
    assert len(cpu) == 2500
    _compare_rows(cpu, tpu, check_order=True, approx_float=True,
                  labels=("cpu", "tpu"))
    (batch,) = handed_on
    assert batch["bucket"] == 4096 == max(bucket_rows(2500), floor)
    assert type(batch["row_count"]) is int and batch["row_count"] == 2500
    assert summary["sized_stages"] == 1
    assert syncs_by_site == {"stage-size": 1}


#: rows kept -> the bucket handed on, floor 2,048, input bucket 8,192
EDGES = {0: FLOOR, 1: FLOOR, FLOOR: FLOOR, FLOOR + 1: 2 * FLOOR,
         4097: BUCKET, ROWS: BUCKET}


@pytest.mark.parametrize("kept", list(EDGES))
def test_the_edges_of_the_count(kept, floor, handed_on, syncs_by_site):
    cpu, tpu, summary = _both(
        f"select k, s, dn from t where k < {kept}", _table())
    assert len(cpu) == kept
    _compare_rows(cpu, tpu, check_order=True, approx_float=True,
                  labels=("cpu", "tpu"))
    (batch,) = handed_on
    assert batch["bucket"] == EDGES[kept]
    assert type(batch["row_count"]) is int and batch["row_count"] == kept
    # one fetch whatever was kept, also where nothing shrinks
    assert summary["sized_stages"] == 1
    assert syncs_by_site == {"stage-size": 1}


def test_a_stage_that_kept_nothing_hands_the_join_a_known_empty_side(
        floor, handed_on, syncs_by_site):
    """The build side filtered to nothing is known to be empty, so the
    join above it builds and probes nothing."""
    dim = {"k": np.arange(ROWS), "a": np.arange(ROWS) % 7}
    fact = {"k": np.arange(500) * 3, "v": np.arange(500) * 0.5}
    rows, summaries = [], []
    for s in (cpu_session(), tpu_session()):
        try:
            s.create_or_replace_temp_view("dim", s.create_dataframe(dim))
            s.create_or_replace_temp_view("fact", s.create_dataframe(fact))
            before = dict(SC.stats()["traces_by_kind"])
            rows.append(s.sql("select fact.k, v from fact, dim where "
                              "fact.k = dim.k and dim.a = 9").collect())
            after = SC.stats()["traces_by_kind"]
        finally:
            s.stop()
    assert rows == [[], []]
    assert [b["row_count"] for b in handed_on
            if b["bucket"] == FLOOR] == [0]
    built = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert not any(k.startswith("join.") and n for k, n in built.items()), \
        built
    assert tracing.last_query_summary()["sized_stages"] == 1


@pytest.mark.parametrize("rows, bucket", [(1500, 2048), (2048, 2048),
                                          (300, 1024)])
def test_a_batch_at_or_under_the_floor_keeps_the_one_program(
        rows, bucket, floor, handed_on, syncs_by_site):
    table = _table(rows)
    text = "select k, s, dn from t where k % 3 = 0"
    s = tpu_session()
    try:
        s.create_or_replace_temp_view("t", s.create_dataframe(table))
        s.sql(text).collect()           # builds the program
        del handed_on[:]
        syncs_by_site.clear()
        tpu = s.sql(text).collect()     # the steady call
        summary = tracing.last_query_summary()
    finally:
        s.stop()
    c = cpu_session()
    c.create_or_replace_temp_view("t", c.create_dataframe(table))
    _compare_rows(c.sql(text).collect(), tpu, check_order=True,
                  approx_float=True, labels=("cpu", "tpu"))
    (batch,) = handed_on
    assert batch["bucket"] == bucket
    assert isinstance(batch["row_count"], DeferredCount)
    assert summary["sized_stages"] == 0
    assert "stage-size" not in syncs_by_site
    assert summary["dispatches_by_kind"]["fused.stage"] == 1
    assert "fused.compact" not in summary["dispatches_by_kind"]


def test_a_stage_without_a_filter_is_never_sized(floor, handed_on,
                                                 syncs_by_site):
    cpu, tpu, summary = _both("select k + 1 as j, d * 2 as e from t",
                              _table())
    _compare_rows(cpu, tpu, check_order=True, approx_float=True,
                  labels=("cpu", "tpu"))
    assert [b["bucket"] for b in handed_on] in ([], [BUCKET])
    assert summary["sized_stages"] == 0
    assert "stage-size" not in syncs_by_site


def test_a_sized_stage_is_two_dispatches_and_one_fetch(floor,
                                                       syncs_by_site):
    s = tpu_session()
    try:
        s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
        text = "select k, s from t where i = {}"
        s.sql(text.format(0)).collect()
        syncs_by_site.clear()
        s.sql(text.format(1)).collect()
        summary = tracing.last_query_summary()
        syncs = dict(syncs_by_site)
        explained = s.sql(text.format(2)).explain(analyze=True)
    finally:
        s.stop()
    assert summary["dispatches_by_kind"]["fused.stage"] == 1
    assert summary["dispatches_by_kind"]["fused.compact"] == 1
    assert summary["sized_stages"] == 1
    assert syncs == {"stage-size": 1}
    assert summary["transitions"]["sync_count"] == 1
    assert "sized_stages=1" in explained


def test_a_surviving_dictionary_column_keeps_its_codes(floor, handed_on,
                                                       tmp_path):
    """A dictionary-encoded string column that passes a sized stage is
    still codes against the same dictionary, at the sized bucket."""
    from spark_rapids_tpu.columnar.encoding import DictionaryColumn
    rng = np.random.default_rng(5)
    cats = np.array(["alpha", "beta", "gamma", "delta", "epsilon"])
    s = cats[rng.integers(0, 5, ROWS)].astype(object)
    s[rng.random(ROWS) < 0.1] = None
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"s": pa.array(s),
                             "k": pa.array(cats[rng.integers(0, 5, ROWS)]),
                             "v": pa.array(np.arange(ROWS))}),
                   path, row_group_size=ROWS)
    rows = []
    for sess in (cpu_session(), tpu_session()):
        try:
            sess.create_or_replace_temp_view("t", sess.read.parquet(path))
            # (a comparison with a literal alone is the scan's to prune)
            rows.append(sess.sql("select s, k, v from t where v % 4 < 3 "
                                 "and s <> 'gamma'").collect())
        finally:
            sess.stop()
    _compare_rows(rows[0], rows[1], check_order=True, approx_float=True,
                  labels=("cpu", "tpu"))
    sized = [b for b in handed_on if type(b["row_count"]) is int]
    assert len(sized) == 1
    assert sized[0]["bucket"] == max(bucket_rows(len(rows[0])), FLOOR)
    assert [isinstance(c, DictionaryColumn)
            for c in sized[0]["columns"]] == [True, True, False]
    assert tracing.last_query_summary()["sized_stages"] == 1


def test_a_second_literal_inside_the_bucket_builds_no_program(floor):
    """The shapes follow the bucket of the count and nothing finer: 857
    and 606 rows both take the floor's bucket."""
    s = tpu_session()
    try:
        s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
        text = "select k, s, d from t where k % 7 = {} and i < {}"
        counts, built = [], []
        for a, b in ((3, 5), (5, 2)):
            before = SC.stats()
            counts.append(len(s.sql(text.format(a, b)).collect()))
            after = SC.stats()
            built.append(after["compiles"] + after["traces"]
                         - before["compiles"] - before["traces"])
            assert tracing.last_query_summary()["sized_stages"] == 1
    finally:
        s.stop()
    assert counts == [857, 606]
    assert built[0] > 0 and built[1] == 0


def test_sized_stages_are_the_stage_size_syncs_of_the_process(
        floor, syncs_by_site):
    s = tpu_session()
    try:
        s.create_or_replace_temp_view("t", s.create_dataframe(_table()))
        before = TR.totals()["sync_count"]
        sized = syncs = 0
        for lit in (1, 2, 3):
            s.sql(f"select k, d from t where i = {lit}").collect()
            summary = tracing.last_query_summary()
            sized += summary["sized_stages"]
            syncs += summary["transitions"]["sync_count"]
        after = TR.totals()["sync_count"]
    finally:
        s.stop()
    # the fetch is the queries' only sync, so the ledger holds as many
    assert syncs_by_site == {"stage-size": 3}
    assert sized == 3 == syncs == after - before


def test_overlapping_served_queries_add_up_to_the_process(
        floor, syncs_by_site):
    """Two streams through one ``QueryServer``: each summary counts its
    own sized stages, and together they are the process's delta."""
    from spark_rapids_tpu.serving.server import QueryServer
    session = tpu_session()
    server = None
    old_interval = sys.getswitchinterval()
    try:
        session.create_or_replace_temp_view(
            "t", session.create_dataframe(_table()))
        session.create_or_replace_temp_view(
            "u", session.create_dataframe(_table(1500)))
        # stream 0: a sized stage a text; stream 1: every other text stays
        # under the floor
        texts = [["select k, s from t where i = {}".format(j)
                  for j in range(4)],
                 [("select k, s from t where i < {}" if j % 2 else
                   "select k, s from u where i < {}").format(j)
                  for j in range(4)]]
        want = [[1, 1, 1, 1], [0, 1, 0, 1]]
        for stream in texts:
            for text in stream[:2]:
                session.sql(text).collect()     # builds the programs
        server = QueryServer(session=session)
        sys.setswitchinterval(1e-4)
        errors = []

        def client(i):
            try:
                for j, text in enumerate(texts[i]):
                    server.submit(text, tag=f"sz.s{i}.{j}").result(600)
            except BaseException as e:  # noqa: BLE001 - shown below
                errors.append(e)

        syncs_by_site.clear()
        before = TR.totals()["sync_count"]
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        assert not errors, errors
        after = TR.totals()["sync_count"]
        summaries = [[next(s for s in tracing.recent_summaries()
                           if s["description"] == f"serve:sz.s{i}.{j}")
                      for j in range(4)] for i in range(2)]
    finally:
        sys.setswitchinterval(old_interval)
        if server is not None:
            server.stop()
        session.stop()
    assert [[s["sized_stages"] for s in stream]
            for stream in summaries] == want
    assert syncs_by_site["stage-size"] == sum(map(sum, want))
    assert sum(s["transitions"]["sync_count"] for stream in summaries
               for s in stream) == after - before


@pytest.fixture
def programs_looked_up(monkeypatch):
    """``(kind, key)`` of every stage program looked up."""
    seen = []
    real = SC.get_or_build

    def recording(kind, key, build):
        seen.append((kind, key))
        return real(kind, key, build)

    monkeypatch.setattr(SC, "get_or_build", recording)
    return seen


#: the star texts of the benchmark's ``store_star_join`` (the demographic
#: dimension filtered by three strings on the build side; q27 with a
#: ROLLUP above the joins), on the repo's TPC-DS tables at sf 0.02 in one
#: partition: ``customer_demographics`` is 1,920 rows in a 2,048-row
#: bucket, of which the strings keep one in 70
STAR_FLOOR = 1024


@pytest.mark.parametrize("q", ["q7", "q27"])
def test_a_star_text_builds_its_demographic_side_at_the_sized_bucket(
        q, patched_floor, handed_on, programs_looked_up):
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    patched_floor(STAR_FLOOR)
    rows = []
    for s in (cpu_session(),
              tpu_session({"spark.rapids.sql.test.enabled": "false"})):
        try:
            register_tables(s, sf=0.02, num_partitions=1)
            rows.append(s.sql(QUERIES[q]).collect())
        finally:
            s.stop()
    _compare_rows(rows[0], rows[1], check_order=False, approx_float=True,
                  labels=("cpu", "tpu"))
    summary = tracing.last_query_summary()
    # customer_demographics (2,048-row bucket) and date_dim (1,461 rows in
    # 2,048) are over the patched floor; the other dimensions are under it
    assert summary["sized_stages"] == 2
    assert [b["bucket"] for b in handed_on
            if type(b["row_count"]) is int] == [STAR_FLOOR, STAR_FLOOR]
    # no build side is sorted at the dimension's own bucket any more: the
    # key columns' shapes in ``join.build``'s program keys are the floor's
    built = [key[1][0][1][0] for kind, key in programs_looked_up
             if kind == "join.build"]
    assert len(built) == 4 and set(built) == {STAR_FLOOR}, built


def test_a_rollup_over_a_filtered_scan_fans_out_the_sized_bucket(
        floor, syncs_by_site):
    """No join below the roll-up: the fan-out's three copies take the
    bucket the filter's stage hands on (``expand_rows_padded``), which is
    the floor's where the scan's was 8,192 rows."""
    table = _table()
    table["g"] = np.arange(ROWS) % 3
    table["h"] = np.arange(ROWS) % 2
    cpu, tpu, summary = _both(
        "select g, h, sum(d) sd, count(*) c from t where k % 12 < 3 "
        "group by rollup(g, h)", table)
    _compare_rows(cpu, tpu, check_order=False, approx_float=True,
                  labels=("cpu", "tpu"))
    assert len(tpu) == 7
    assert summary["sized_stages"] == 1
    assert summary["expand_rows_padded"] == 3 * FLOOR
    assert syncs_by_site.get("stage-size") == 1
