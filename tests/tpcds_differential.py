"""TPC-DS differential check, shared by the shard files
(``tests/test_tpcds_<k>.py``): every query runs on the CPU and TPU engines
over identical synthetic data and the row sets must agree.

The queries are spread over ``N_SHARDS`` collected files because xdist's
``--dist loadfile`` hands a whole file to one worker: as one file the 60
cases were the suite's wall.  The shard is a function of the query's name,
so a query added to ``testing/tpcds_queries.py`` lands in a file with no
file edited.
"""

import re

from spark_rapids_tpu.testing.tpcds import register_tables
from spark_rapids_tpu.testing.tpcds_queries import QUERIES

from tests.asserts import assert_tpu_and_cpu_are_equal_collect

N_SHARDS = 8


def _number(qname):
    return int(re.match(r"q(\d+)", qname).group(1))


def shard(k):
    """Sorted names of the queries whose number is ``k`` modulo
    ``N_SHARDS``."""
    return sorted(q for q in QUERIES if _number(q) % N_SHARDS == k)


def check(qname):
    def fn(session):
        register_tables(session, sf=0.02)
        return session.sql(QUERIES[qname])
    assert_tpu_and_cpu_are_equal_collect(
        fn, ignore_order=True,
        conf={"spark.rapids.sql.test.enabled": "false"})
