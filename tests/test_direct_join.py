"""Direct-address joins (``ops/join_ops.py``): a build side whose one
integral key is unique and spans no more slots than the hash path's
table is probed by ``table[key - min]``, one gather a probe row, with no
verify and no pair compaction.  Every case holds the direct path's answer
to the hash path's (the same session with the direct path taken away)
and to the CPU engine's, and reads ``direct_joins`` and
``probe_gather_rounds`` off the query's summary.  Joins the rule sends to
the hash path (duplicate, multi-key, string, floating, too widely spread
and null-safe keys) must answer there with no direct probe."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.columnar.column import SIZED_MIN_BUCKET, bucket_rows
from spark_rapids_tpu.ops import join_ops as J

from tests.asserts import _compare_rows, cpu_session, tpu_session

#: the key's type and the base its values sit at: an int64 base far
#: above 32 bits, dates either side of the epoch, dictionary codes
KEY_TYPES = {"int": 1_000, "long": 1 << 40, "date": -20, "code-space": 10}

#: a shared dictionary: a code-space key joins only where both sides
#: carry the same one
_WORDS = pa.array([f"w{i:04d}" for i in range(200)])


def _keys(values, kind):
    """An arrow key column of ``kind``; ``None`` is a null key."""
    if kind == "code-space":
        return pa.DictionaryArray.from_arrays(pa.array(values, pa.int32()),
                                              _WORDS)
    return pa.array(values, {"int": pa.int32(), "long": pa.int64(),
                             "date": pa.date32()}[kind])


def _dim(kind, n=40):
    """Unique keys from the base with gaps, two null keys."""
    base = KEY_TYPES[kind]
    keys = [base + 2 * i for i in range(n)] + [None, None]
    return pa.table({"k": _keys(keys, kind),
                     "rv": pa.array(np.arange(len(keys)) * 7.5)})


def _fact(kind, n=1_500, seed=3):
    """Keys from ten below the dimension's least to ten above its
    greatest (misses below min and above max), a tenth of them null; a
    bucket over the dimension's, so an inner join builds on the
    dimension."""
    rng = np.random.default_rng(seed)
    base = KEY_TYPES[kind]
    keys = [None if rng.random() < 0.1 else base + int(v)
            for v in rng.integers(-10, 90, n)]
    return pa.table({"k": _keys(keys, kind),
                     "lv": pa.array(rng.normal(size=n))})


def _join(how, kind, fact=_fact, dim=_dim):
    """The DataFrame of one join type; ``swapped`` is an inner join whose
    small LEFT side the runtime picks as the build side."""
    def df(s):
        f = s.create_dataframe(fact(kind))
        d = s.create_dataframe(dim(kind))
        if how == "swapped":
            return d.join(f, on="k", how="inner")
        if how == "condition":
            return f.join(d, on="k", how="inner",
                          condition=F.col("lv") * 10 < F.col("rv"))
        return f.join(d, on="k", how=how)
    return df


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


def _tpu(df_fn):
    s = tpu_session()
    try:
        return df_fn(s).collect(), tracing.last_query_summary()
    finally:
        s.stop()


def _answers(df_fn):
    """The summaries of the TPU's run and of its run with the direct path
    taken away, once the CPU engine's rows, the direct run's and the hash
    run's are shown to be the same rows."""
    cpu = df_fn(cpu_session()).collect()
    rows, summary = _tpu(df_fn)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(J, "_direct_side", lambda *args: None)
        hashed, hashed_summary = _tpu(df_fn)
    _compare_rows(cpu, rows, False, True, labels=("cpu", "direct"))
    _compare_rows(cpu, hashed, False, True, labels=("cpu", "hash"))
    assert hashed_summary["direct_joins"] == 0
    assert hashed_summary["probe_gather_rounds"] == J.PROBE_GATHER_ROUNDS
    return summary


def _assert_direct(summary, probes=1):
    assert summary["direct_joins"] == probes
    assert summary["probe_gather_rounds"] == J.DIRECT_GATHER_ROUNDS * probes


def _assert_hash(summary, probes=1):
    assert summary["direct_joins"] == 0
    assert summary["probe_gather_rounds"] == J.PROBE_GATHER_ROUNDS * probes


@pytest.mark.parametrize("kind", list(KEY_TYPES))
@pytest.mark.parametrize("how", ["inner", "left", "right", "full", "semi",
                                 "anti", "swapped", "condition"])
def test_direct_answers_as_the_hash_path_and_the_cpu(how, kind):
    """Nulls on both sides, probe keys below the least build key and above
    the greatest, for every join type and every key type that qualifies."""
    _assert_direct(_answers(_join(how, kind)))


def _keyed(values, kind="long"):
    return lambda _kind: pa.table({
        "k": _keys(values, kind),
        "rv": pa.array(np.arange(len(values), dtype=np.float64))})


@pytest.mark.parametrize("how", ["inner", "left", "full", "anti"])
@pytest.mark.parametrize("build", [[], [None] * 6],
                         ids=["empty", "all-null"])
def test_a_build_side_with_no_live_key_is_direct(build, how):
    _assert_direct(_answers(_join(how, "long", dim=_keyed(build))))


_TOP, _BOTTOM = (1 << 63) - 1, -(1 << 63)


def _probe_of(values):
    return lambda _kind: pa.table({
        "k": pa.array(values, pa.int64()),
        "lv": pa.array(np.arange(len(values), dtype=np.float64))})


#: the int64 extremes: a narrow span at either end is direct, and no
#: probe key overflows ``key - min``; a span past 64 bits is not
EXTREMES = {
    "top-alone": ([_TOP], True),
    "bottom-narrow": ([_BOTTOM, _BOTTOM + 3, _BOTTOM + 5], True),
    "plus-minus-2^62": ([1 << 62, -(1 << 62)], False),
    "plus-minus-2^63-1": ([_TOP, -_TOP], False),
    "both-ends": ([_TOP, _BOTTOM, 0], False),
}


@pytest.mark.parametrize("case", list(EXTREMES))
def test_int64_extremes(case):
    build, direct = EXTREMES[case]
    probe = [_TOP, _BOTTOM, _BOTTOM + 3, 0, 1 << 62, -(1 << 62), -_TOP,
             _TOP - 1, None]
    summary = _answers(_join("left", "long", fact=_probe_of(probe),
                             dim=_keyed(build)))
    (_assert_direct if direct else _assert_hash)(summary)


def _span_dim(span):
    """Ten unique keys whose span is ``span``."""
    keys = [5] + [5 + span - 1 - i for i in range(9)]
    return _keyed(keys)


def _cap(build_rows, probe_rows):
    """The slots of the hash path's table for the two sides' buckets."""
    return 1 << J._table_bits(bucket_rows(build_rows),
                              bucket_rows(probe_rows))


@pytest.mark.parametrize("over", [0, 1], ids=["at-the-cap", "one-over"])
def test_the_span_is_capped_by_the_hash_paths_slots(over):
    span = _cap(10, 280) + over
    summary = _answers(_join("left", "long", fact=_probe_of(
        [5, 6, 5 + span - 1, 5 + span - 2, 5 + span, 4, None] * 40),
        dim=_span_dim(span)))
    (_assert_hash if over else _assert_direct)(summary)


def test_duplicate_build_keys_take_the_hash_path(syncs_by_site):
    """The duplicate is found by the build program: one ``join-build``
    fetch, then the hash path's table."""
    summary = _answers(_join("left", "long",
                             dim=_keyed([(1 << 40) + 12, (1 << 40) + 7,
                                         (1 << 40) + 12, None])))
    _assert_hash(summary)
    assert syncs_by_site.get("join-build") == 1      # the direct run's


@pytest.mark.parametrize("case", ["null-safe", "two-keys", "string",
                                  "double"])
def test_joins_the_rule_refuses_take_the_hash_path_with_no_fetch(
        case, syncs_by_site):
    """Decided from the join's keys alone: no build program of the direct
    path runs, so no ``join-build`` fetch."""
    rng = np.random.default_rng(9)
    fact = {"k": rng.integers(0, 30, 200), "k2": rng.integers(0, 3, 200),
            "lv": rng.normal(size=200)}
    dim = {"k": np.arange(25), "k2": np.arange(25) % 3,
           "rv": np.arange(25) * 1.5}
    on = ["k", "k2"] if case == "two-keys" else "k"
    if case == "string":
        fact["k"] = np.array([f"s{v}" for v in fact["k"]], dtype=object)
        dim["k"] = np.array([f"s{v}" for v in dim["k"]], dtype=object)
    if case == "double":
        fact["k"] = fact["k"] * 0.5
        dim["k"] = dim["k"] * 0.5

    def df(s):
        return s.create_dataframe(fact).join(
            s.create_dataframe(dim), on=on, how="left",
            null_safe=case == "null-safe")
    _assert_hash(_answers(df))
    assert "join-build" not in syncs_by_site


@pytest.mark.parametrize("rows, sized", [(3_000, False), (50_000, True)],
                         ids=["speculative", "sized"])
def test_small_and_large_probe_batches(rows, sized, syncs_by_site):
    """A probe at or under the floor: its pair table is its bucket, with
    no headroom and no overflow flag for the collect to check.  Over the
    floor: sized by the fetched total, as every large join is."""
    rng = np.random.default_rng(5)
    table = pa.table({"k": pa.array(rng.integers(0, 2_000, rows)),
                      "lv": pa.array(rng.normal(size=rows))})
    fact = lambda _kind: table  # noqa: E731
    dim = _keyed(list(range(0, 2_000, 3)))
    cpu = _join("inner", "long", fact=fact, dim=dim)(cpu_session()).collect()
    syncs_by_site.clear()
    rows_out, summary = _tpu(_join("inner", "long", fact=fact, dim=dim))
    _compare_rows(cpu, rows_out, False, True, labels=("cpu", "direct"))
    _assert_direct(summary)
    assert summary["sized_joins"] == int(sized)
    assert summary["speculation_replays"] == 0
    out = max(bucket_rows(len(cpu)), SIZED_MIN_BUCKET) if sized \
        else bucket_rows(rows)
    assert summary["pair_rows_padded"] == out
    want = {"join-build": 1, "join-size": 1} if sized else {"join-build": 1}
    assert syncs_by_site == want


def test_the_probe_reads_its_live_rows_alone():
    """``_direct_probe`` on a probe column whose padding rows carry keys
    the table holds, marked valid: only the first ``row_count`` rows may
    find their build row, each the one whose key it holds."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    build_keys = np.array([40, 12, 30, 7, 25], dtype=np.int64)
    bucket = 1024
    data = np.zeros(bucket, dtype=np.int64)
    data[:5] = build_keys
    build = ColumnarBatch([DeviceColumn(
        jnp.asarray(data), jnp.asarray(np.arange(bucket) < 5), 5, T.LONG)],
        5, ["k"])
    probe = np.full(bucket, 30, dtype=np.int64)       # padding: a hit
    probe[:6] = [12, 6, 41, 30, 7, 30]
    pcol = DeviceColumn(jnp.asarray(probe), jnp.ones(bucket, dtype=bool), 6,
                        T.LONG)
    built = J.build_side(build, (0,), [pcol], (False,))
    assert built.direct is not None and built.gather_rounds == 1
    rows, counts, offsets, total = (np.asarray(a) for a in
                                    J._probe_ranges([pcol], built))
    np.testing.assert_array_equal(rows[:6], [1, -1, -1, 2, 3, 2])
    assert (counts[6:] == 0).all() and int(total) == 4
    np.testing.assert_array_equal(offsets, np.cumsum(counts) - counts)
