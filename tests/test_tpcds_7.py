import pytest

from tests.tpcds_differential import check, shard


@pytest.mark.parametrize("qname", shard(7))
def test_tpcds_query_differential(qname):
    check(qname)
