"""Differential join tests: every join type, nulls, duplicates, strings,
conditions, broadcast vs shuffled (reference: integration_tests
join_test.py patterns over assert_gpu_and_cpu_are_equal_collect)."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux import tracing

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

    built = J.BuiltSide(batch(), (0,), jnp.zeros(n, dtype=np.uint64),
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
    #: a jaxpr's loops; both lower to an HLO ``while``
    LOOPS = {"while", "scan"}
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
