"""The comparison that decides ``correct`` has to fail what is wrong: an
answer altered where it is produced, and the float32 control."""

import copy

import numpy as np
import pytest

from benchmark.compare import compare, ordered
from benchmark.datagen import tpcds as G
from benchmark.literals import Query
from benchmark.run import load_by_name
from test_datagen import SMALL

ROWS = dict(SMALL, store_sales=288040, item=18000, promotion=300)
TEXTS = ("q3", "q42", "q52", "q55", "q7", "q19", "q27")


@pytest.fixture(scope="module")
def db():
    return G.StoreChannel(ROWS, 77)


def served(answer):
    rows = ordered(answer)[:answer["limit"]]
    return [{k: (float(v) if isinstance(v, np.floating) else v)
             for k, v in r.items()} for r in rows]


def draw(name, seed=1):
    q = Query(name)
    return q.nth(int(np.random.default_rng(seed).integers(q.domain_size())))


@pytest.mark.parametrize("name", TEXTS)
def test_the_reference_agrees_with_itself(db, name):
    answer = load_by_name("reference", name).run(db, draw(name))
    got = compare(served(answer), answer)
    assert got["rows_wrong"] == 0 and got["max_rel_err"] == 0.0
    assert got["groups"] > 0


@pytest.mark.parametrize("name", TEXTS)
def test_the_float32_control_reads_far_above_float64(db, name):
    ref = load_by_name("reference", name)
    worst = 0.0
    for seed in range(4):
        p = draw(name, seed)
        got = compare(served(ref.run(db, p, dtype=np.float32)), ref.run(db, p))
        worst = max(worst, got["max_rel_err"])
    assert worst > 1e-8


FAULTS = {
    "a double off by one part in a million":
        lambda rows, f: rows[0].__setitem__(f, rows[0][f] * (1 + 1e-6)),
    "an integer or string altered":
        lambda rows, f: rows[0].__setitem__(
            next(k for k in rows[0] if k != f and rows[0][k] is not None
                 and not isinstance(rows[0][k], float)), "altered"),
    "a row left out": lambda rows, f: rows.pop(0),
    "a row twice": lambda rows, f: rows.append(dict(rows[-1])),
    "two rows swapped": lambda rows, f: rows.__setitem__(
        slice(0, 2), [rows[1], rows[0]]),
    "a null where a value belongs":
        lambda rows, f: rows[0].__setitem__(f, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_answer_is_wrong(db, fault):
    answer = load_by_name("reference", "q3").run(db, draw("q3"))
    rows = copy.deepcopy(served(answer))
    assert len(rows) >= 2
    FAULTS[fault](rows, "sum_agg")
    got = compare(rows, answer)
    assert got["rows_wrong"] > 0 or got["max_rel_err"] > 1e-9


def test_a_row_that_limit_should_have_kept_is_missed(db):
    answer = load_by_name("reference", "q7").run(db, draw("q7"))
    full = ordered(answer)
    assert len(full) > answer["limit"]
    rows = served(answer)
    rows[-1] = dict(full[answer["limit"]])      # the 101st in place of the 100th
    assert compare(rows, answer)["rows_wrong"] > 0


def test_sums_equal_to_rounding_may_stand_in_either_order():
    answer = {"columns": ["k", "s"], "float_columns": ["s"],
              "order": [("s", "desc"), ("k", "asc")], "limit": 2,
              "rows": [{"k": 1, "s": 100.0}, {"k": 2, "s": 100.0 + 1e-12},
                       {"k": 3, "s": 5.0}]}
    assert compare([{"k": 1, "s": 100.0}, {"k": 2, "s": 100.0}],
                   answer)["rows_wrong"] == 0
    assert compare([{"k": 2, "s": 100.0}, {"k": 1, "s": 100.0}],
                   answer)["rows_wrong"] == 0
    assert compare([{"k": 3, "s": 5.0}, {"k": 1, "s": 100.0}],
                   answer)["rows_wrong"] > 0
