"""A join hands on only the columns its consumers read.

Column pruning (``plan/pruning.py``) gives a join its ``selection``: the
columns of ``left ++ right`` some consumer above names.  Keys and
filter-only columns are still read from the children, and are not
gathered at the pair bucket (``ops/join_ops.py`` ``gather_join_output``).
Every case holds the pruned plan's answer to the CPU engine's and to the
same session with column pruning off, and reads what the joins hand on
off the plan and ``join_cols_pruned`` off the query's summary."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.exec import joins as JX
from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
from spark_rapids_tpu.plan.overrides import TpuOverrides

from tests.asserts import _compare_rows, cpu_session, tpu_session

NO_PRUNING = {"spark.rapids.sql.columnPruning.enabled": "false"}


def _joins(plan):
    """The plan's joins, bottom-up (the first join of a text first)."""
    out = []

    def walk(node):
        for c in node.children:
            walk(c)
        if isinstance(node, JX._JoinBase):
            out.append(node)
    walk(plan)
    return out


# ---------------------------------------------------------------------------
# the benchmark's texts on the generator's tables
# ---------------------------------------------------------------------------

#: what each join of a text hands on, in SQL order: the columns anything
#: above the join reads
HANDED_ON = {
    "q3": [{"d_year", "ss_item_sk", "ss_ext_sales_price"},
           {"d_year", "i_brand_id", "i_brand", "ss_ext_sales_price"}],
    "q55": [{"ss_item_sk", "ss_ext_sales_price"},
            {"i_brand_id", "i_brand", "ss_ext_sales_price"}],
    "q27_qual": [{"ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                  "ss_quantity", "ss_list_price", "ss_sales_price",
                  "ss_coupon_amt"},
                 {"ss_item_sk", "ss_store_sk", "ss_quantity",
                  "ss_list_price", "ss_sales_price", "ss_coupon_amt"},
                 {"ss_item_sk", "ss_quantity", "ss_list_price",
                  "ss_sales_price", "ss_coupon_amt", "s_state"},
                 {"ss_quantity", "ss_list_price", "ss_sales_price",
                  "ss_coupon_amt", "s_state", "i_item_id"}],
    "q7_qual": [{"ss_sold_date_sk", "ss_item_sk", "ss_promo_sk",
                 "ss_quantity", "ss_list_price", "ss_sales_price",
                 "ss_coupon_amt"},
                {"ss_item_sk", "ss_promo_sk", "ss_quantity",
                 "ss_list_price", "ss_sales_price", "ss_coupon_amt"},
                {"ss_promo_sk", "ss_quantity", "ss_list_price",
                 "ss_sales_price", "ss_coupon_amt", "i_item_id"},
                {"ss_quantity", "ss_list_price", "ss_sales_price",
                 "ss_coupon_amt", "i_item_id"}],
}
#: the columns the children deliver that each join leaves, summed over a
#: text's joins: one output batch a join at this scale
LEFT_OUT = {"q3": 3 + 3, "q55": 4 + 3, "q27_qual": 5 + 3 + 2 + 2,
            "q7_qual": 5 + 3 + 2 + 4}
SEED = 2147493319
#: store_sales 144,020 rows, as ``tests/partitioned_store.py``: a smaller
#: fact table leaves q27's demographic triple no row
SCALE_DOWN = 20


@pytest.fixture(scope="module")
def store():
    from benchmark import run as bench
    from benchmark.literals import Query
    cell = bench.Cell("store_star_join", SCALE_DOWN)
    cell.queries = {q: Query(q) for q in HANDED_ON}
    gen, tables = bench.make_tables(cell, SEED)
    params = {q: cell.queries[q].nth(0) for q in HANDED_ON}
    s = tpu_session()
    for name, table in tables.items():
        s.create_or_replace_temp_view(name, s.create_dataframe(table))
    yield {"session": s, "gen": gen, "params": params,
           "texts": {q: cell.queries[q].fill(params[q]) for q in HANDED_ON}}
    s.stop()


@pytest.mark.parametrize("text", sorted(HANDED_ON))
def test_the_joins_of_a_text_hand_on_what_is_read_above(store, text):
    plan = store["session"].sql(store["texts"][text])._executed_plan()
    joins = _joins(plan)
    assert [set(j.schema.names) for j in joins] == HANDED_ON[text]
    # no join hands on a column twice, and each reads its keys below
    for j in joins:
        assert len(j.schema.names) == len(set(j.schema.names))
        assert j.cols_pruned > 0
    assert sum(j.cols_pruned for j in joins) == LEFT_OUT[text]


@pytest.mark.parametrize("text", sorted(HANDED_ON))
def test_join_cols_pruned_counts_and_the_reference_agrees(store, text):
    from benchmark import run as bench
    from benchmark.compare import compare
    rows = store["session"].sql(store["texts"][text]).collect()
    assert tracing.last_query_summary()["join_cols_pruned"] == \
        LEFT_OUT[text]
    answer = bench.load_by_name("reference", text).run(
        store["gen"], store["params"][text])
    got = compare(rows, answer)
    assert got["groups"] > 0, "the draw keeps no row: nothing compared"
    assert got["rows_wrong"] == 0 and got["max_rel_err"] <= 1e-9, got


# ---------------------------------------------------------------------------
# every kind of join, against the CPU engine and the unpruned plan
# ---------------------------------------------------------------------------

def _register(s, parts=1):
    f = {"fk": [i % 7 if i % 11 else None for i in range(60)],
         "a": list(range(60)),
         "flt": [i % 3 for i in range(60)],
         "s": [f"x{i % 5}" for i in range(60)]}
    d = {"k": list(range(2, 9)), "b": [10 * i for i in range(7)],
         "c": [f"c{i}" for i in range(7)]}
    s.create_or_replace_temp_view("f", s.create_dataframe(
        f, num_partitions=parts))
    s.create_or_replace_temp_view("d", s.create_dataframe(
        d, num_partitions=parts))


def _sql(text, parts=1):
    def run(s):
        _register(s, parts)
        return s.sql(text)
    return run


def _frames(how):
    def run(s):
        _register(s)
        d = s.table("d").with_column_renamed("k", "fk")
        return s.table("f").join(d, on="fk", how=how).select("a")
    return run


#: (recipe, extra conf, does some join leave a column out)
CASES = {
    "inner": (_sql("select a, b from f join d on f.fk = d.k "
                   "where flt = 1"), {}, True),
    "inner-grouped": (_sql("select c, sum(a) s from f join d "
                           "on f.fk = d.k where s = 'x1' group by c"),
                      {}, True),
    "count-only": (_sql("select count(*) n from f join d on f.fk = d.k"),
                   {}, True),
    "left-outer": (_sql("select a, c from f left join d on f.fk = d.k"),
                   {}, True),
    "right-outer": (_sql("select b, s from f right join d on f.fk = d.k"),
                    {}, True),
    "full-outer": (_sql("select a, c from f full join d on f.fk = d.k"),
                   {}, True),
    "left-semi": (_frames("left_semi"), {}, True),
    "left-anti": (_frames("left_anti"), {}, True),
    "non-equi": (_sql("select a, c from f join d on f.fk = d.k "
                      "and d.b > f.a"), {}, True),
    "non-equi-outer": (_sql("select b from f right join d on f.fk = d.k "
                            "and f.a > 30"), {}, True),
    "nested-loop": (_sql("select a, c from f join d on f.a < d.b"),
                    {}, True),
    "swapped-build": (_sql("select d.c, f.s from d join f on d.k = f.fk"),
                      {}, True),
    "broadcast": (_sql("select a, c from f join d on f.fk = d.k", 3),
                  {}, True),
    "shuffled": (_sql("select a, c from f join d on f.fk = d.k", 3),
                 {"spark.sql.autoBroadcastJoinThreshold": "-1"}, True),
    "sub-partition": (_sql("select a, c from f join d on f.fk = d.k"),
                      {"spark.rapids.sql.join.subPartitionThresholdBytes":
                       "1"}, True),
    "self-join": (_sql("select x.a, y.a from f x join f y on x.fk = y.fk "
                       "where x.a < 9"), {}, True),
    "rollup": (_sql("select c, s, sum(a) t from f join d on f.fk = d.k "
                    "group by rollup(c, s)"), {}, True),
    "every-column": (_sql("select * from f join d on f.fk = d.k"),
                     {}, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_pruned_join_answers_as_the_cpu_and_the_unpruned_plan(case):
    recipe, conf, pruned = CASES[case]
    want = recipe(cpu_session()).collect()
    s = tpu_session(conf)
    df = recipe(s)
    got = df.collect()
    summary = tracing.last_query_summary()
    joins = _joins(df._executed_plan())
    assert joins
    assert any(j.selection is not None for j in joins) == pruned
    assert (summary["join_cols_pruned"] > 0) == pruned
    unpruned = recipe(tpu_session(dict(conf, **NO_PRUNING))).collect()
    assert tracing.last_query_summary()["join_cols_pruned"] == 0
    _compare_rows(want, got, False, True, labels=("cpu", "tpu"))
    _compare_rows(unpruned, got, False, True, labels=("unpruned", "pruned"))


def test_the_plan_kinds_the_cases_name(monkeypatch):
    """The broadcast, shuffled and sub-partition cases take the join they
    are named for, and the swapped case builds on its left side."""
    def kinds(case):
        recipe, conf, _ = CASES[case]
        s = tpu_session(dict(conf, **{"spark.rapids.sql.test.enabled":
                                      "false"}))
        plan = TpuOverrides(s.conf).apply(recipe(s)._plan)
        return {type(j).__name__ for j in _joins(plan)}
    assert kinds("broadcast") == {"TpuBroadcastHashJoinExec"}
    assert kinds("shuffled") == {"TpuSubPartitionHashJoinExec"}
    calls = []
    real = JX._TpuJoinCore._join_device

    def spy(self, probe, build, build_cache=None, swapped=False):
        calls.append(swapped)
        return real(self, probe, build, build_cache, swapped)
    monkeypatch.setattr(JX._TpuJoinCore, "_join_device", spy)
    recipe, conf, _ = CASES["swapped-build"]
    recipe(tpu_session(conf)).collect()
    assert calls == [True]
    calls.clear()
    recipe, conf, _ = CASES["sub-partition"]
    recipe(tpu_session(conf)).collect()
    # one call a sub-partition bucket
    assert len(calls) == 16


def test_a_join_whose_every_column_is_read_has_no_selection():
    s = tpu_session()
    _register(s)
    df = s.sql("select * from f join d on f.fk = d.k")
    plan = df._executed_plan()
    (join,) = _joins(plan)
    assert join.selection is None and join.cols_pruned == 0
    assert join.schema.names == ["fk", "a", "flt", "s", "k", "b", "c"]


# ---------------------------------------------------------------------------
# what reads a join's output by position
# ---------------------------------------------------------------------------

def _agg_above_pruned_key(s, n=4):
    s.set_conf("spark.sql.autoBroadcastJoinThreshold", "-1")
    rng = np.random.default_rng(11)
    left = {"k": rng.integers(0, 40, 3000).astype(np.int64),
            "v": rng.integers(0, 9, 3000).astype(np.int64)}
    right = {"k": rng.integers(0, 40, 2000).astype(np.int64),
             "w": rng.integers(0, 9, 2000).astype(np.int64)}
    a = s.create_dataframe(left, num_partitions=n)
    b = s.create_dataframe(right, num_partitions=n)
    return (a.join(b, on="k").group_by("v")
            .agg(F.sum("w").alias("sw"), F.count("w").alias("n")))


def test_an_aggregate_above_a_join_whose_key_went_keeps_its_exchange():
    """The join's left side is hash-placed by ``k`` (output column 0).
    The join hands on ``v`` and ``w`` only, so ``v`` is its column 0 now:
    the placement by ``k`` is no longer delivered, and the aggregate's
    exchange by ``v`` stays."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    ov = TpuOverrides(s.conf)
    final = ov.apply(_agg_above_pruned_key(s)._plan)
    (join,) = _joins(final)
    assert join.schema.names == ["v", "w"]
    assert ov.last_elided == []
    exchanges = {id(n) for n in final.collect_nodes()
                 if isinstance(n, CpuShuffleExchangeExec) or
                 type(n).__name__ == "TpuShuffleExchangeExec"}
    assert len(exchanges) == 3, final.tree_string()
    want = sorted(map(str, _agg_above_pruned_key(cpu_session()).collect()))
    got = sorted(map(str, _agg_above_pruned_key(tpu_session()).collect()))
    assert got == want


def test_the_plan_signature_sees_the_selection():
    from spark_rapids_tpu.serving.signature import plan_signature
    s = tpu_session()
    _register(s)
    plan = s.sql("select a, b from f join d on f.fk = d.k")._plan
    (join,) = _joins(plan)
    narrowed = JX.CpuShuffledHashJoinExec(
        join.left_keys, join.right_keys, join.join_type, join.condition,
        join.left, join.right, join.null_safe, (1, 5))
    assert narrowed.schema.names == ["a", "b"]
    widened = narrowed.with_children([join.left, join.right])
    assert widened.selection == (1, 5)
    assert plan_signature(narrowed).norm != plan_signature(join).norm
    assert plan_signature(widened).norm == plan_signature(narrowed).norm


# ---------------------------------------------------------------------------
# gather_join_output: the maps by a slice
# ---------------------------------------------------------------------------

def _side(n, bucket, base):
    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    b = batch_from_pydict({"x": [base + i for i in range(n)],
                           "t": [f"t{base + i}" if i % 4 else None
                                 for i in range(n)]}).to_device()
    assert b.bucket == bucket
    return b


def _parents_rows(probe, build, l_map, r_map, count, cols):
    """The rows the all-columns gather of the parent gave: row ``i`` of
    the output is the probe's row ``l_map[i]`` and the build's row
    ``r_map[i]``, a side's columns null where its map is negative."""
    ph, bh = probe.to_host().to_pydict(), build.to_host().to_pydict()
    sides = [(ph, l_map, k) for k in ph] + [(bh, r_map, k) for k in bh]
    out = []
    for i in range(count):
        row = []
        for o in cols:
            table, m, name = sides[o]
            j = -1 if m is None else int(m[i])
            row.append(table[name][j] if j >= 0 else None)
        out.append(tuple(row))
    return out


@pytest.mark.parametrize("maps_len,out_bucket,count", [
    (1024, 1024, 700),      # at: the slice is the identity
    (2048, 1024, 1000),     # above: the slice drops the tail
    (1024, 2048, 1024),     # below: padded with -1 past the maps
])
@pytest.mark.parametrize("cols", [None, (3, 0), (1,)])
def test_gather_join_output_by_slice_gives_the_parents_rows(
        maps_len, out_bucket, count, cols):
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.join_ops import gather_join_output
    probe, build = _side(900, 1024, 0), _side(50, 1024, 5000)
    rng = np.random.default_rng(maps_len + out_bucket)
    l_map = rng.integers(-1, 900, maps_len)
    r_map = rng.integers(-1, 50, maps_len)
    out = gather_join_output(probe, build, jnp.asarray(l_map),
                             jnp.asarray(r_map), count,
                             out_bucket=out_bucket, cols=cols)
    assert out.bucket == out_bucket and out.row_count == count
    order = list(range(4)) if cols is None else list(cols)
    host = out.to_host()
    got = list(zip(*[host.columns[j].to_pylist()
                     for j in range(len(order))]))
    assert got == _parents_rows(probe, build, l_map, r_map, count, order)


def test_gather_join_output_with_one_side_absent():
    """A semi/anti join's empty right side, and the unmatched build rows of
    a right outer join (no probe map: its columns all null)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.join_ops import gather_join_output
    probe, build = _side(10, 1024, 0), _side(30, 1024, 100)
    r_map = np.arange(1024) % 30
    out = gather_join_output(probe, build, None, jnp.asarray(r_map), 20,
                             out_bucket=1024, cols=(2, 1))
    host = out.to_host()
    assert host.columns[0].to_pylist() == [100 + i for i in range(20)]
    assert host.columns[1].to_pylist() == [None] * 20
