import glob
import json
import os

import pytest

from benchmark.literals import PLACEHOLDER, LiteralPool, Query

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = sorted(os.path.basename(p)[:-4] for p in
               glob.glob(os.path.join(HERE, "..", "queries", "*.sql")))


@pytest.mark.parametrize("name", NAMES)
def test_every_text_has_a_domain_for_each_placeholder(name):
    q = Query(name)
    assert set(PLACEHOLDER.findall(q.template)) == set(q.params)
    assert q.domain_size() == q.distinct_texts
    text = q.fill(q.nth(0))
    assert not PLACEHOLDER.search(text)


@pytest.mark.parametrize("name", NAMES)
def test_nth_walks_the_whole_domain_once(name):
    q = Query(name)
    seen = {json.dumps(q.nth(i), sort_keys=True)
            for i in range(q.domain_size())}
    assert len(seen) == q.domain_size()


def test_a_pool_repeats_no_text_until_the_domain_is_used_up():
    q = {"q42": Query("q42")}
    pool = LiteralPool(q, 2**31 + 7)
    first = [json.dumps(pool.draw("q42"), sort_keys=True) for _ in range(10)]
    assert len(set(first)) == 10
    again = [json.dumps(pool.draw("q42"), sort_keys=True) for _ in range(10)]
    assert set(again) == set(first)


def test_the_same_seed_draws_the_same_and_another_seed_another_order():
    q = {"q3": Query("q3")}
    a = [LiteralPool(q, 5).draw("q3") for _ in range(1)]
    b = [LiteralPool(q, 5).draw("q3") for _ in range(1)]
    assert a == b
    one, other = LiteralPool(q, 5), LiteralPool(q, 6)
    assert [one.draw("q3") for _ in range(8)] != \
        [other.draw("q3") for _ in range(8)]


def test_draws_stay_inside_the_templates_domains():
    q = Query("q55")
    for index in range(0, q.domain_size(), 7):
        v = q.nth(index)
        assert 1 <= v["MANAGER"] <= 100 and v["MONTH"] in (11, 12)
        assert 1998 <= v["YEAR"] <= 2002
