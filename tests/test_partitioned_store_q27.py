"""The star text q27_qual (four joins, the demographic dimension's
three string attributes, ROLLUP(i_item_id, s_state)) in Spark's partitioned layout: the benchmark generator's
tables at a twentieth of SF1 in 1, 4 and 10 partitions.  A third of
``tests/test_partitioned_store.py``, in a file of its own so that another
xdist worker takes its compiles."""

import pytest

from tests.partitioned_store import (
    LAYOUTS, STAR_PLANS, answered, assert_every_dimension_broadcast_and_built_once,
    assert_reference_answer, assert_shuffled_same_rows, parents_plans)

TEXT = "q27_qual"


@pytest.fixture(scope="module")
def store():
    got = answered((TEXT,), STAR_PLANS)
    yield got
    for s in got["sessions"].values():
        s.stop()


@pytest.mark.parametrize("n", LAYOUTS)
def test_every_layout_gives_the_reference_answer(store, n):
    assert_reference_answer(store, n, TEXT)


def test_every_join_shuffled_gives_the_same_rows(store):
    assert_shuffled_same_rows(store, 4, TEXT)


def test_the_one_partition_plan_is_byte_for_byte_the_parents(store):
    assert store["runs"][1, "rule", TEXT]["explain"] == parents_plans()[TEXT]


@pytest.mark.parametrize("n", LAYOUTS[1:])
def test_every_dimension_is_broadcast_and_built_once(store, n):
    assert_every_dimension_broadcast_and_built_once(store, n, TEXT)
