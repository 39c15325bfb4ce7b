"""Differential join tests: every join type, nulls, duplicates, strings,
conditions, broadcast vs shuffled (reference: integration_tests
join_test.py patterns over assert_gpu_and_cpu_are_equal_collect)."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.columnar.column import SIZED_MIN_BUCKET as SIZED_FLOOR

from tests.asserts import assert_tpu_and_cpu_are_equal_collect


def _left_data():
    return {
        "k": [1, 2, 2, 3, None, 5, None, 7, 8, 2],
        "lv": [10.0, 20.0, 21.0, 30.0, 40.0, None, 60.0, 70.0, 80.0, 22.0],
    }


def _right_data():
    return {
        "k": [2, 2, 3, 4, None, 6, 8, 8, None],
        "rv": [200.0, 201.0, 300.0, 400.0, None, 600.0, 800.0, 801.0, 900.0],
    }


JOIN_TYPES = ["inner", "left", "right", "full", "semi", "anti"]


@pytest.mark.parametrize("how", JOIN_TYPES)
@pytest.mark.parametrize("nparts", [1, 3])
def test_join_basic(how, nparts):
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data(), num_partitions=nparts)
        .join(s.create_dataframe(_right_data(), num_partitions=2), on="k",
              how=how),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_broadcast_join(how):
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data(), num_partitions=3)
        .join(F.broadcast(s.create_dataframe(_right_data())), on="k",
              how=how),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_multi_key(how):
    left = {"a": [1, 1, 2, 2, None, 3], "b": [1, 2, 1, None, 1, 3],
            "lv": [1, 2, 3, 4, 5, 6]}
    right = {"a": [1, 2, 2, None, 3, 4], "b": [2, 1, 1, 1, 3, 4],
             "rv": [10, 20, 21, 30, 40, 50]}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(left, num_partitions=2)
        .join(s.create_dataframe(right, num_partitions=2), on=["a", "b"],
              how=how),
        ignore_order=True)


def test_join_null_safe():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data())
        .join(s.create_dataframe(_right_data()), on="k", how="inner",
              null_safe=True),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_join_string_keys(how):
    left = {"k": ["apple", "pear", None, "fig", "apple", ""],
            "lv": [1, 2, 3, 4, 5, 6]}
    right = {"k": ["apple", "fig", "fig", None, "", "plum"],
             "rv": [10, 20, 21, 30, 40, 50]}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(left, num_partitions=2)
        .join(s.create_dataframe(right, num_partitions=2), on="k", how=how),
        ignore_order=True)


def test_join_float_keys_nan_negzero():
    # Spark join keys: NaN == NaN, -0.0 == 0.0
    left = {"k": [float("nan"), -0.0, 1.5, 2.5, None],
            "lv": [1, 2, 3, 4, 5]}
    right = {"k": [float("nan"), 0.0, 1.5, 3.5, None],
             "rv": [10, 20, 30, 40, 50]}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(left)
        .join(s.create_dataframe(right), on="k", how="inner"),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_with_condition(how):
    # extra non-equi condition over the pair (reference: AST join conditions)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data(), num_partitions=2)
        .join(s.create_dataframe(_right_data(), num_partitions=2), on="k",
              how=how, condition=F.col("lv") * 10 < F.col("rv")),
        ignore_order=True)


def test_cross_join():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe({"a": [1, 2, 3]})
        .cross_join(s.create_dataframe({"b": [10, 20]})),
        ignore_order=True)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_nested_loop_condition_join(how):
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe({"a": [1, 2, 3, 4, None]},
                                     num_partitions=2)
        .join(s.create_dataframe({"b": [2, 3, 3, 9]}), on=None, how=how,
              condition=F.col("a") < F.col("b")),
        ignore_order=True)


@pytest.mark.parametrize("how", JOIN_TYPES)
def test_join_empty_sides(how):
    empty = {"k": np.array([], dtype=np.int64),
             "rv": np.array([], dtype=np.float64)}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data())
        .join(s.create_dataframe(empty), on="k", how=how),
        ignore_order=True)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(empty, num_partitions=1)
        .select(F.col("k"), F.Alias(F.col("rv"), "lv"))
        .join(s.create_dataframe(_right_data()), on="k", how=how),
        ignore_order=True)


#: the two sizings of a hash join's pair table: the speculative bucket
#: (default), and the exact one a replay after an overflow runs with
SIZINGS = [{}, {"spark.rapids.sql.join.speculativeSizing.enabled": "false"}]
SIZING_IDS = ["speculative", "exact"]


@pytest.mark.parametrize("sizing, copies, replays", [
    (SIZINGS[0], 1, 0), (SIZINGS[1], 1, 0), (SIZINGS[0], 400, 1)],
    ids=SIZING_IDS + ["speculative-overflow-replay"])
def test_join_duplicate_key_explosion(sizing, copies, replays):
    # many-to-many: 4x3 matches for k=1; 400 copies of the left side make
    # 4,800 pairs from a 2,048-row probe bucket, past the speculative pair
    # table's headroom, so the action runs again with exact sizing
    left = {"k": [1, 1, 1, 1, 2] * copies, "lv": list(range(5 * copies))}
    right = {"k": [1, 1, 1, 3], "rv": [10, 20, 30, 40]}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(left)
        .join(s.create_dataframe(right), on="k", how="inner"),
        ignore_order=True, conf=sizing)
    assert tracing.last_query_summary()["speculation_replays"] == replays


#: a hash join sizes its pair table and the batch it hands on by what its
#: probe batch's bucket says (``exec/joins.py``): above
#: ``SIZED_MIN_BUCKET`` by the probe's candidate total, fetched; at or
#: under it speculatively, with no fetch.  Each case: probe rows, matches a
#: probe row finds (a fraction: the share of rows that find one), and what
#: the query's summary and the gateway must then read: ``sized_joins``,
#: ``direct_joins``, ``pair_rows_padded``, the join's output bucket, syncs
#: by site.  A dimension of unique keys is a direct table (its build fetch
#: is site ``join-build``): a small probe's pair table is its bucket, with
#: no overflow flag for the collect to check
SIZED_CASES = {
    # 65,536-row probe bucket, a hundredth of the rows join: the floor
    "over-the-floor-selective": (50_000, 0.01, dict(
        sized=1, direct=1, pair_rows=SIZED_FLOOR, out_bucket=SIZED_FLOOR,
        syncs={"join-build": 1, "join-size": 1})),
    # 200,000 pairs from a 65,536-row probe bucket: past bucket x headroom,
    # which a speculative join answers by running the whole query twice;
    # four build rows a key, so the hash path
    "over-the-floor-fan-out": (50_000, 4, dict(
        sized=1, direct=0, pair_rows=1 << 18, out_bucket=1 << 18,
        syncs={"join-build": 1, "join-size": 1})),
    # a probe bucket AT the floor: no fetch of the pair count
    "at-the-floor": (30_000, 0.01, dict(
        sized=0, direct=1, pair_rows=SIZED_FLOOR, out_bucket=SIZED_FLOOR,
        syncs={"join-build": 1})),
    "small": (3_000, 0.01, dict(
        sized=0, direct=1, pair_rows=4096, out_bucket=4096,
        syncs={"join-build": 1})),
}


@pytest.fixture
def syncs_by_site(monkeypatch):
    """The blocking syncs the gateway records, by site."""
    from spark_rapids_tpu.aux import transitions as TR
    seen = {}
    record = TR._record_sync

    def counting(site, *args, **kwargs):
        seen[site] = seen.get(site, 0) + 1
        return record(site, *args, **kwargs)

    monkeypatch.setattr(TR, "_record_sync", counting)
    return seen


def _fact_and_dim(n, matches):
    """``n`` fact rows over 1,000 keys; the dimension holds ``matches``
    rows a key, or, for a fraction, one row for that share of the keys."""
    rng = np.random.default_rng(7)
    fact = {"k": rng.integers(0, 1000, n), "lv": rng.normal(size=n)}
    keys = np.arange(1000) if matches >= 1 else \
        np.arange(int(1000 * matches)) * int(1 / matches)
    dim = {"k": np.repeat(keys, max(int(matches), 1))}
    dim["rv"] = np.arange(len(dim["k"]), dtype=np.float64)
    return fact, dim


def _join_buckets(summary):
    """The padded rows each of the summary's hash joins handed on, the
    plan's last join first."""
    return [sum(p["padded_rows"] for p in n["partitions"])
            for n in summary["nodes"] if "HashJoin" in n["node"]]


@pytest.mark.parametrize("case", list(SIZED_CASES))
def test_join_sized_by_what_its_probe_bucket_says(case, syncs_by_site):
    assert SIZED_FLOOR == 1 << 15
    n, matches, want = SIZED_CASES[case]
    fact, dim = _fact_and_dim(n, matches)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(fact)
        .join(s.create_dataframe(dim), on="k", how="inner"),
        ignore_order=True)
    summary = tracing.last_query_summary()
    assert summary["speculation_replays"] == 0
    assert summary["sized_joins"] == want["sized"]
    assert summary["direct_joins"] == want["direct"]
    assert summary["pair_rows_padded"] == want["pair_rows"]
    assert _join_buckets(summary) == [want["out_bucket"]]
    # the sized join pays its one fetch and registers no overflow flag,
    # so the collect has none to check; nor does a direct one
    assert syncs_by_site == want["syncs"]
    assert summary["transitions"]["sync_count"] == sum(want["syncs"].values())


@pytest.mark.parametrize("first_keeps, handed_on, sized, pair_rows, syncs", [
    # the first join keeps 500 of 50,000 rows and hands on the floor's
    # bucket: the second probes at 32,768 rows, speculatively, and its
    # direct table (five unique keys) leaves it no overflow to flag
    (0.01, SIZED_FLOOR, 1, SIZED_FLOOR + SIZED_FLOOR,
     {"join-build": 2, "join-size": 1}),
    # the first keeps 40,000 and hands on 65,536 rows: the second is over
    # the floor too, and keeps a tenth of them
    (0.8, 1 << 16, 2, (1 << 16) + SIZED_FLOOR,
     {"join-build": 2, "join-size": 2}),
], ids=["second-at-the-floor", "second-over-the-floor"])
def test_chained_joins_probe_at_the_bucket_the_first_hands_on(
        first_keeps, handed_on, sized, pair_rows, syncs, syncs_by_site):
    fact, dim = _fact_and_dim(50_000, first_keeps)
    rng = np.random.default_rng(11)
    fact["k2"] = rng.integers(0, 50, len(fact["k"]))
    dim2 = {"k2": np.arange(5), "rv2": np.arange(5) * 1.5}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(fact)
        .join(s.create_dataframe(dim), on="k", how="inner")
        .join(s.create_dataframe(dim2), on="k2", how="inner"),
        ignore_order=True)
    summary = tracing.last_query_summary()
    assert summary["speculation_replays"] == 0
    assert summary["sized_joins"] == sized
    assert summary["pair_rows_padded"] == pair_rows
    assert _join_buckets(summary) == [SIZED_FLOOR, handed_on]
    assert syncs_by_site == syncs


def test_sized_join_second_literal_traces_nothing():
    """A sized join's shapes follow its candidate total's bucket and
    nothing finer: a literal that keeps another count on the same side of
    every bucket edge (516 and 493 rows, both under the floor) builds no
    program."""
    from spark_rapids_tpu.exec import stage_compiler as SC
    from tests.asserts import tpu_session
    fact, dim = _fact_and_dim(50_000, 1)
    dim["a"] = dim["k"] % 100
    s = tpu_session()
    try:
        s.create_or_replace_temp_view("fact", s.create_dataframe(fact))
        s.create_or_replace_temp_view("dim", s.create_dataframe(dim))
        text = ("select dim.k, sum(lv) s, count(*) c from fact, dim "
                "where fact.k = dim.k and dim.a = {} group by dim.k")
        counts, traces = [], []
        for literal in (3, 7):
            before = SC.stats()["traces"]
            rows = s.sql(text.format(literal)).collect()
            traces.append(SC.stats()["traces"] - before)
            counts.append(sum(r["c"] for r in rows))
            summary = tracing.last_query_summary()
            assert summary["sized_joins"] == 1
            assert summary["pair_rows_padded"] == SIZED_FLOOR
        assert counts == [516, 493]
        assert traces[0] > 0 and traces[1] == 0
    finally:
        s.stop()


def test_join_then_aggregate():
    # joins compose with downstream device aggregation
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data(), num_partitions=2)
        .join(s.create_dataframe(_right_data(), num_partitions=2), on="k",
              how="inner")
        .group_by("k").agg(F.Alias(F.sum("rv"), "s"),
                           F.Alias(F.count("*"), "c")),
        ignore_order=True)


@pytest.mark.parametrize("sizing", SIZINGS, ids=SIZING_IDS)
def test_join_larger_random(sizing):
    rng = np.random.default_rng(42)
    n, m = 5000, 3000
    left = {"k": rng.integers(0, 500, n), "lv": rng.normal(size=n)}
    right = {"k": rng.integers(0, 500, m), "rv": rng.normal(size=m)}
    for how in ("inner", "left", "full"):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: s.create_dataframe(left, num_partitions=3)
            .join(s.create_dataframe(right, num_partitions=2), on="k",
                  how=how),
            ignore_order=True, conf=sizing)


def _offsets(counts):
    counts = np.asarray(counts, dtype=np.int64)
    return np.cumsum(counts) - counts


#: (counts per probe row, out_bucket): the pair table's positions, against
#: the binary search that the expansion replaced
EXPAND_CASES = {
    "leading-zero-rows": ([0, 0, 0, 2, 1, 3, 1, 1], 8),
    "interior-zero-rows": ([2, 0, 0, 1, 0, 3, 0, 2], 8),
    "trailing-zero-rows": ([1, 2, 1, 0, 0, 0, 0, 0], 8),
    "all-counts-zero": ([0] * 8, 8),
    "one-row": ([3], 8),
    "one-empty-row": ([0], 4),
    "duplicate-keys": ([4, 3, 5, 1], 16),
    "out-bucket-below-probe-bucket": ([0, 1, 0, 0, 1, 0, 0, 0, 1, 0, 0, 0,
                                      0, 0, 1, 0], 4),
    "out-bucket-equals-probe-bucket": ([1, 0, 2, 1, 0, 0, 3, 1], 8),
    "out-bucket-above-probe-bucket": ([5, 0, 9, 2], 32),
    "total-fills-out-bucket": ([2, 2, 2, 2], 8),
    "total-above-out-bucket": ([3, 0, 4, 6, 0, 5, 2, 7], 8),
    "total-above-2^31": ([3, 1 << 31, 2, 0, 1 << 33, 1, 0, 5], 8),
    # narrowed without the guard, 2^32 + 2 would wrap to position 2
    "offset-that-would-wrap-to-a-live-position": ([1, (1 << 32) + 1, 4, 0],
                                                  8),
    "blocked-prefix-sum": (np.random.default_rng(5).integers(0, 4, 2048),
                           4096),
}


@pytest.mark.parametrize("case", list(EXPAND_CASES))
def test_expand_positions_matches_the_search(case):
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.batch_ops import expand_positions
    counts, out_bucket = EXPAND_CASES[case]
    offsets = _offsets(counts)
    want = np.searchsorted(offsets, np.arange(out_bucket), "right") - 1
    got = expand_positions(jnp.asarray(offsets), out_bucket, jnp)
    assert got.dtype == np.int32 and got.shape == (out_bucket,)
    np.testing.assert_array_equal(np.asarray(got), want)


#: a jaxpr's loops; both lower to an HLO ``while``
LOOPS = {"while", "scan"}


def _pair_program_args(n):
    """``_expand_verify``'s arguments for one LONG key over ``n``-row
    buckets on both sides, no candidates."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.ops import join_ops as J

    def batch():
        return ColumnarBatch([DeviceColumn(
            jnp.zeros(n, dtype=np.int64), jnp.ones(n, dtype=bool), n,
            T.LONG)], n, ["k"])

    built = J.BuiltSide(
        batch(), (0,),
        jnp.zeros((1 << J._table_bits(n, n)) + 1, dtype=np.int32),
        jnp.zeros(n, dtype=np.int32), [1])
    zeros = jnp.zeros(n, dtype=np.int64)
    return batch(), (0,), built, (False,), zeros, zeros, jnp.int64(0)


def test_join_pair_expands_without_a_search_loop():
    """The pair expansion is a histogram and a prefix sum: nothing under
    the ``join.pair`` program's ``expand`` scope may loop per probe row, as
    ``jnp.searchsorted`` does (23 rounds of an 8.4M-row gather a join at
    TPC-DS SF1; PERF.md section 6, PR 29)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import join_ops as J
    from spark_rapids_tpu.testing import tpu_compile as TC
    n = 1024
    prog, specs = TC.ProgramRecorder().capture(
        J._expand_verify, *_pair_program_args(n), 2 * n)
    assert prog.kind == "join.pair"
    expand = TC.primitives_under_scope(prog._fn, specs, "expand")
    assert "scatter-add" in expand and "gather" in expand, expand
    assert not LOOPS & expand, expand

    @jax.jit
    def searched(offsets):
        with jax.named_scope("expand"):
            return jnp.searchsorted(offsets, jnp.arange(2 * n), "right")

    # the guard sees the loop it guards against
    assert LOOPS & TC.primitives_under_scope(
        searched, (jax.ShapeDtypeStruct((n,), np.int64),), "expand")


def test_join_pair_refuses_positions_past_32_bits():
    from spark_rapids_tpu.ops import join_ops as J
    with pytest.raises(ValueError, match="32-bit positions"):
        J._expand_verify(*_pair_program_args(1024), 1 << 31)


def test_join_probe_looks_up_without_a_search_loop():
    """The probe reads its candidate range from the build side's
    bucket-start table: ``PROBE_GATHER_ROUNDS`` gathers a probe row under
    the ``join.probe`` program's ``lookup`` scope and no loop, where two
    ``jnp.searchsorted`` made 32 to 44 rounds of a 64-bit gather (6.70 s
    of a TPC-DS q3's 17.4 s; PERF.md section 6, PR 32)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import join_ops as J
    from spark_rapids_tpu.testing import tpu_compile as TC
    n = 1024
    probe, _ords, built = _pair_program_args(n)[:3]
    prog, specs = TC.ProgramRecorder().capture(
        J._probe_ranges, [probe.columns[0]], built)
    assert prog.kind == "join.probe"
    lookup = TC.primitive_counts_under_scope(prog._fn, specs, "lookup")
    assert lookup["gather"] == J.PROBE_GATHER_ROUNDS, lookup
    assert not LOOPS & set(lookup), lookup
    # no search anywhere else in the program either
    assert "while" not in str(prog._fn.trace(*specs).jaxpr)

    @jax.jit
    def searched(hs, h):
        with jax.named_scope("lookup"):
            return jnp.searchsorted(hs, h, side="left")

    # the guard sees the loop it guards against
    u64 = jax.ShapeDtypeStruct((n,), np.uint64)
    assert LOOPS & TC.primitives_under_scope(searched, (u64, u64), "lookup")


def _long_column(values, bucket):
    """A LONG key column of ``bucket`` rows; ``None`` is a null key."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import DeviceColumn
    data = np.zeros(bucket, dtype=np.int64)
    valid = np.zeros(bucket, dtype=bool)
    for i, v in enumerate(values):
        if v is not None:
            data[i], valid[i] = v, True
    return DeviceColumn(jnp.asarray(data), jnp.asarray(valid), len(values),
                        T.LONG)


_RNG = np.random.default_rng(11)
#: (build keys, build bucket, probe keys, probe bucket)
PROBE_CASES = {
    "empty-build": ([], 1024, list(range(300)), 1024),
    "one-row-build": ([7], 1024, [7, 8, 7, None, 9], 1024),
    "all-null-keys": ([None] * 40, 1024, [None] * 25 + [1, 2, 3], 1024),
    "heavy-duplicates": ([5] * 600 + [6] * 300 + list(range(100, 200)),
                         1024,
                         list(_RNG.integers(0, 210, 900)), 1024),
    "probe-padding": (list(range(1000)), 1024,
                      list(_RNG.integers(0, 2000, 37)), 2048),
    "build-padding": (list(_RNG.integers(0, 5000, 1025)), 2048,
                      list(_RNG.integers(0, 5000, 2000)), 2048),
    "build-fills-its-bucket": (list(range(1024)), 1024,
                               list(_RNG.integers(0, 1024, 1024)), 1024),
}


@pytest.mark.parametrize("case", list(PROBE_CASES))
def test_probe_ranges_contain_the_searched_ranges(case):
    """Every probe row's ``[lo, lo + count)`` holds the exact range of
    equal hashes that ``np.searchsorted`` finds among the live build
    rows, no range reaches a padding row, and the candidate total passes
    the exact one by what the table's load allows."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.ops import join_ops as J
    bkeys, b_bucket, pkeys, p_bucket = PROBE_CASES[case]
    bcol, pcol = _long_column(bkeys, b_bucket), _long_column(pkeys, p_bucket)
    built = J._hash_side(ColumnarBatch([bcol], len(bkeys), ["k"]), (0,),
                         [pcol])
    lo, counts, offsets, total = (np.asarray(a) for a in
                                  J._probe_ranges([pcol], built))
    assert lo.dtype == np.int32 and counts.dtype == offsets.dtype == np.int64

    def hashes(c, n):
        return np.asarray(J._hash_rows(
            [c], [1], jnp.arange(c.bucket) < n, jnp))
    hb, hp = hashes(bcol, len(bkeys)), hashes(pcol, len(pkeys))
    perm = np.asarray(built.perm)
    hs = hb[perm][:len(bkeys)]              # live rows first, by hash
    np.testing.assert_array_equal(hs, np.sort(hb[:len(bkeys)]))
    assert sorted(perm[:len(bkeys)]) == list(range(len(bkeys)))
    starts = np.asarray(built.starts)
    assert starts[0] == 0 and starts[-1] == len(bkeys)
    assert (np.diff(starts) >= 0).all()
    live = slice(0, len(pkeys))
    want_lo = np.searchsorted(hs, hp[live], "left")
    want_hi = np.searchsorted(hs, hp[live], "right")
    assert (lo[live] <= want_lo).all()
    assert (lo[live] + counts[live] >= want_hi).all()
    assert (lo + counts <= len(bkeys)).all()        # never a padding row
    assert (counts[len(pkeys):] == 0).all()
    np.testing.assert_array_equal(offsets, np.cumsum(counts) - counts)
    exact = int((want_hi - want_lo).sum())
    largest_run = max(np.unique(hs, return_counts=True)[1], default=0)
    assert exact <= total == counts.sum()
    # a probe row meets the other live rows of its slot: live build rows /
    # slots of them on average, which the load holds under 1 / _TABLE_LOAD
    mean_false = len(pkeys) * len(bkeys) / (len(starts) - 1)
    assert mean_false <= len(pkeys) / J._TABLE_LOAD
    assert total - exact <= \
        mean_false + 4 * mean_false ** 0.5 + 2 * largest_run + 8


@pytest.mark.parametrize("bits", [0, 1], ids=["one-slot", "two-slots"])
@pytest.mark.parametrize("how", JOIN_TYPES)
def test_join_types_when_nearly_every_candidate_is_false(how, bits,
                                                         monkeypatch):
    """With a table of one or two slots a probe row's candidates are all,
    or half, of the live build rows: ``verify`` alone decides what joins,
    for every join type, the non-equi condition and null keys."""
    from spark_rapids_tpu.ops import join_ops as J
    monkeypatch.setattr(J, "_table_bits", lambda bucket, probe: bits)
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(_left_data(), num_partitions=2)
        .join(s.create_dataframe(_right_data(), num_partitions=2), on="k",
              how=how),
        ignore_order=True)
    if how in ("inner", "left", "full"):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: s.create_dataframe(_left_data())
            .join(s.create_dataframe(_right_data()), on="k", how=how,
                  condition=F.col("lv") * 10 < F.col("rv")),
            ignore_order=True)
    assert tracing.last_query_summary()["speculation_replays"] == 0


@pytest.mark.parametrize("build, probe, slots", [
    # the build side alone sets the table where the probe side asks for
    # no more: _TABLE_LOAD slots a row of its bucket
    (1024, 1024, 8 << 10), (1 << 15, 1 << 15, 8 << 15),
    (1 << 17, 1 << 19, 8 << 17), (1 << 21, 1 << 22, 8 << 21),
    (1 << 24, 1 << 22, 8 << 24),
    # capped: a table is 512 MiB at most, whatever the build side
    (1 << 25, 1 << 22, 1 << 27), (1 << 30, 1 << 22, 1 << 27),
    # a small dimension under the fact table's bucket: _PROBE_LOAD slots
    # a probe row
    (1 << 15, 1 << 22, 1 << 23), (1024, 1 << 18, 1 << 19),
    # the probe's share stops at the largest table whose lookups are flat
    (1 << 15, 1 << 25, 1 << 23),
])
def test_table_bits_follow_the_larger_ask_of_the_two_buckets(
        build, probe, slots):
    """False candidates size a large join's pair table and everything
    above it, so a large probe side widens a small build side's table;
    a function of the two buckets alone, so of the programs' shapes."""
    from spark_rapids_tpu.ops import join_ops as J
    assert J._TABLE_MAX_BITS == 24 + J._TABLE_LOAD.bit_length() - 1
    assert 1 << J._table_bits(build, probe) == slots
