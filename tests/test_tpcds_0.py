import pytest

from spark_rapids_tpu.testing.tpcds import register_tables
from spark_rapids_tpu.testing.tpcds_queries import QUERIES

from tests.tpcds_differential import N_SHARDS, check, shard


@pytest.mark.parametrize("qname", shard(0))
def test_tpcds_query_differential(qname):
    check(qname)


def test_shards_cover_every_query_once():
    # as lists: a name in two shards would show as a duplicate
    names = [q for k in range(N_SHARDS) for q in shard(k)]
    assert sorted(names) == sorted(QUERIES)


def test_tpcds_queries_return_rows():
    """Sanity: the synthetic data actually produces output for
    representative queries (guards against a datagen regression making the
    differential tests vacuously pass on empty sets).  q2 (weekly sales
    ratios) and q7 (demographic filter) always hit rows."""
    from tests.asserts import cpu_session
    s = cpu_session()
    register_tables(s, sf=0.05)
    assert s.sql(QUERIES["q2"]).collect(), "q2 empty"
    assert s.sql(QUERIES["q7"]).collect(), "q7 empty"


@pytest.mark.slow
def test_r2_q29_after_q20_to_q28():
    """R2's witness (ROADMAP.md R2, docs/compatibility.md): q29 loses a row
    on the device engine when q20 ... q28 ran before it in the process; it
    passes alone and behind q25 alone.  The state that carries over is R2's
    to find; until then the last line fails ("row count differs: 2 vs 1"),
    and `-m 'not slow'` does not pay its three minutes."""
    for n in range(20, 29):
        check(f"q{n}")
    check("q29")
