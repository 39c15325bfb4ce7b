"""Encoded columnar execution tests (ISSUE 11).

Bit-identical-vs-CPU (and vs the eager-decode path) across scan ->
filter -> join -> agg -> sort with ``spark.rapids.sql.encoding.enabled``
on/off, the fallback edge cases (high-cardinality, empty dictionary,
nulls IN the dictionary values), late-materialization white-box checks
(filter output still carries codes), the RLE variant, the compressed
spill tier under forced pool pressure, the planner pass, and AutoTuner
rule 8.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import encoding as ENC
from spark_rapids_tpu.columnar.batch import (HostColumnarBatch,
                                             batch_from_arrow)
from spark_rapids_tpu.columnar.column import HostColumn
from spark_rapids_tpu.columnar.transfer import (download_host_batch,
                                                upload_host_batch)
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.expressions.base import col, lit
from spark_rapids_tpu.session import TpuSession

from tests.asserts import cpu_session, tpu_session, _compare_rows

ENC_OFF = {"spark.rapids.sql.encoding.enabled": "false"}


@pytest.fixture(scope="module")
def enc_parquet(tmp_path_factory):
    """A parquet file whose string columns are dictionary-encoded (the
    pyarrow writer default) with row-level nulls and two row groups."""
    rng = np.random.default_rng(7)
    n = 4000
    cats = np.array(["alpha", "beta", "gamma", "delta", "epsilon"])
    s = cats[rng.integers(0, 5, n)].astype(object)
    s[rng.random(n) < 0.1] = None
    t = pa.table({
        "s": pa.array(s),
        "k": pa.array(cats[rng.integers(0, 5, n)]),
        "v": pa.array(rng.integers(0, 100, n)),
        "f": pa.array(rng.standard_normal(n)),
    })
    path = str(tmp_path_factory.mktemp("encpq") / "t.parquet")
    pq.write_table(t, path, row_group_size=1500)
    return path


def _sessions(extra=None):
    on = tpu_session(extra)
    off = tpu_session(dict(ENC_OFF, **(extra or {})))
    return on, off, cpu_session()


def _assert_trimodal(df_fn, extra=None, ignore_order=True):
    """TPU+encoding vs TPU eager-decode vs CPU: all three agree."""
    on, off, cpu = _sessions(extra)
    r_on = df_fn(on).collect()
    r_off = df_fn(off).collect()
    r_cpu = df_fn(cpu).collect()
    _compare_rows(r_cpu, r_on, check_order=not ignore_order,
                  approx_float=True, labels=("cpu", "tpu-encoded"))
    _compare_rows(r_off, r_on, check_order=not ignore_order,
                  approx_float=True, labels=("tpu-eager", "tpu-encoded"))
    return r_on


# ---------------------------------------------------------------------------
# operator matrix, bit-identical on/off/cpu
# ---------------------------------------------------------------------------

def test_scan_filter_agg_sort_trimodal(enc_parquet):
    s0 = ENC.encoding_stats()

    def fn(s):
        return (s.read.parquet(enc_parquet)
                .filter(col("s") == lit("beta"))
                .groupBy("s")
                .agg(F.sum("v").alias("sv"), F.count("f").alias("c"))
                .order_by("s"))
    rows = _assert_trimodal(fn)
    assert rows, "filter must survive rows"
    s1 = ENC.encoding_stats()
    assert s1["encoded_columns"] > s0["encoded_columns"], \
        "the encoded path never engaged"
    assert s1["decode_avoided_bytes"] > s0["decode_avoided_bytes"]


def test_filter_shapes_trimodal(enc_parquet):
    from spark_rapids_tpu.expressions import predicates as P

    def fn_in(s):
        return s.read.parquet(enc_parquet).filter(
            P.In(col("s"), [lit("alpha"), lit("delta")])).select("s", "v")

    def fn_range(s):
        return s.read.parquet(enc_parquet).filter(
            (col("s") > lit("b")) & (col("s") < lit("e"))).select("s")

    def fn_ne(s):
        return s.read.parquet(enc_parquet).filter(
            col("s") != lit("gamma")).select("s", "f")

    for fn in (fn_in, fn_range, fn_ne):
        _assert_trimodal(fn)


def test_null_accepting_predicates_keep_null_rows(enc_parquet):
    """Review regression (code-space translation dropped null rows): a
    conjunct that is TRUE on null input — IS NULL, coalesce-defaulted
    equality, OR-with-IS-NULL — must keep null rows exactly like the
    row-space path (DictContains binds the conjunct's null-input
    verdict as a runtime arg next to the table)."""
    from spark_rapids_tpu.expressions import predicates as P
    from spark_rapids_tpu.expressions.conditional import Coalesce

    def fn_isnull(s):
        return (s.read.parquet(enc_parquet)
                .filter(P.IsNull(col("s")))
                .agg(F.count("v").alias("c"), F.sum("v").alias("sv")))

    def fn_or(s):
        return (s.read.parquet(enc_parquet)
                .filter(P.Or(P.IsNull(col("s")),
                             P.EqualTo(col("s"), lit("beta"))))
                .select("s", "v"))

    def fn_coalesce(s):
        return (s.read.parquet(enc_parquet)
                .filter(Coalesce(col("s"), lit("beta")) == lit("beta"))
                .select("s", "v"))

    for fn in (fn_isnull, fn_or, fn_coalesce):
        rows = _assert_trimodal(fn)
        assert rows, "null-accepting filter must keep rows"


def test_join_on_dictionary_key_trimodal(enc_parquet):
    def fn(s):
        df = s.read.parquet(enc_parquet)
        small = df.filter(col("v") < lit(10)).select("s", "v")
        return (df.join(small, on="s", how="inner")
                .agg(F.count("v").alias("c"), F.sum("v").alias("sv")))
    _assert_trimodal(fn)


def test_sort_by_dictionary_column_trimodal(enc_parquet):
    def fn(s):
        return (s.read.parquet(enc_parquet)
                .select("s", "k", "v").order_by("s", "k", "v"))
    _assert_trimodal(fn, ignore_order=False)


def test_groupby_two_dict_keys_with_nulls_trimodal(enc_parquet):
    def fn(s):
        return (s.read.parquet(enc_parquet)
                .groupBy("s", "k")
                .agg(F.count("v").alias("c"), F.min("v").alias("mv"))
                .order_by("s", "k"))
    _assert_trimodal(fn, ignore_order=False)


# ---------------------------------------------------------------------------
# fallback edge cases
# ---------------------------------------------------------------------------

def test_high_cardinality_dictionary_falls_back(enc_parquet, tmp_path):
    """Dictionaries above maxDictionarySize decode eagerly at upload —
    bit-identical, with the fallback counted and evented."""
    s0 = ENC.encoding_stats()

    def fn(s):
        return (s.read.parquet(enc_parquet)
                .filter(col("s") == lit("beta"))
                .agg(F.count("v").alias("c")))
    _assert_trimodal(fn, extra={
        "spark.rapids.sql.encoding.maxDictionarySize": "2"})
    s1 = ENC.encoding_stats()
    assert s1["dict_fallbacks"] > s0["dict_fallbacks"]


def test_empty_dictionary_all_null_column(tmp_path):
    t = pa.table({"s": pa.array([None] * 100, type=pa.string()),
                  "v": pa.array(np.arange(100))})
    path = str(tmp_path / "allnull.parquet")
    pq.write_table(t, path)

    def fn(s):
        return (s.read.parquet(path)
                .filter(col("s") == lit("x"))
                .agg(F.count("v").alias("c"), F.count("s").alias("cs")))
    _assert_trimodal(fn)

    def fn2(s):
        return s.read.parquet(path).groupBy("s").agg(
            F.sum("v").alias("sv"))
    _assert_trimodal(fn2)


def test_nulls_in_dictionary_values_fall_back():
    """A dictionary whose VALUES contain null cannot join/group by code
    (a valid code would mean a null row): upload decodes it."""
    vals = pa.array(["aa", None, "cc"])
    d = pa.DictionaryArray.from_arrays(
        pa.array([0, 1, 2, 0, None], type=pa.int32()), vals)
    hb = HostColumnarBatch([HostColumn(d, T.STRING)], 5, ["s"])
    s0 = ENC.encoding_stats()
    dev = upload_host_batch(hb)
    assert not isinstance(dev.columns[0], ENC.DictionaryColumn)
    s1 = ENC.encoding_stats()
    assert s1["dict_fallbacks"] == s0["dict_fallbacks"] + 1
    back = download_host_batch(dev)
    assert back.columns[0].to_pylist() == ["aa", None, "cc", "aa", None]


def test_duplicate_dictionary_values_fall_back():
    vals = pa.array(["aa", "aa", "cc"])
    d = pa.DictionaryArray.from_arrays(
        pa.array([0, 1, 2], type=pa.int32()), vals)
    hc = HostColumn(d, T.STRING)
    assert ENC.classify_host_column(hc) is None


# ---------------------------------------------------------------------------
# late materialization (white box)
# ---------------------------------------------------------------------------

def _encoded_device_batch(values, codes_with_nulls):
    arr = pa.DictionaryArray.from_arrays(
        pa.array(codes_with_nulls, type=pa.int32()), pa.array(values))
    hb = HostColumnarBatch([HostColumn(arr, T.STRING)],
                           len(codes_with_nulls), ["s"])
    return upload_host_batch(hb)


def test_upload_keeps_codes_and_download_ships_codes():
    dev = _encoded_device_batch(["x", "y", "z"], [0, 1, 2, 0, None, 1])
    c = dev.columns[0]
    assert isinstance(c, ENC.DictionaryColumn)
    assert str(c.data.dtype) == "int32"
    assert str(c.data_type) == str(T.STRING)
    s0 = ENC.encoding_stats()
    back = download_host_batch(dev)
    s1 = ENC.encoding_stats()
    assert pa.types.is_dictionary(back.columns[0].arrow.type), \
        "download must reassemble codes, not gather values"
    assert back.columns[0].to_pylist() == ["x", "y", "z", "x", None, "y"]
    assert s1["encoded_bytes_out"] > s0["encoded_bytes_out"]


def test_fused_filter_keeps_output_encoded_and_compiles_once():
    """THE late-materialization contract: a code-space filter's output
    still carries codes (only survivors could ever decode), and two
    different dictionaries + literals share ONE executable."""
    from spark_rapids_tpu.exec import stage_compiler as SC
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec
    from spark_rapids_tpu.expressions.base import BoundReference, Literal
    from spark_rapids_tpu.expressions.predicates import EqualTo
    from spark_rapids_tpu.plan.base import LeafExec

    class _Leaf(LeafExec):
        def __init__(self, batch):
            super().__init__()
            self._b = batch

        @property
        def schema(self):
            return self._b.schema

        @property
        def num_partitions(self):
            return 1

        def execute_partition(self, pidx):
            yield self._b

    def run(values, codes, needle):
        b = _encoded_device_batch(values, codes)
        # the planner's literal promotion makes the conjunct sql (and so
        # the program key) value-independent — string literals are not
        # promotable, but the TABLE mechanism makes them args anyway, so
        # mimic a parameterized chain with a PromotedLiteral by hand
        from spark_rapids_tpu.plan.stages import PromotedLiteral
        pl = PromotedLiteral(needle, T.STRING, 0)
        stage = TpuFusedStageExec(
            [("filter", EqualTo(BoundReference(0, T.STRING, True, "s"),
                                pl))], _Leaf(b))
        # string promoted values do not bind as numpy runtime args; the
        # encoded table IS the runtime binding, so pin _lits empty
        stage._lits = ()
        (out,) = list(stage.execute_partition(0))
        return out

    base = SC.stats()
    out1 = run(["x", "y", "z"], [0, 1, 2, 0, 1, 2, None, 0], "x")
    assert isinstance(out1.columns[0], ENC.DictionaryColumn), \
        "filter output must stay encoded (late materialization)"
    assert out1.columns[0].to_host().to_pylist() == ["x", "x", "x"]
    mid = SC.stats()
    # different dictionary CONTENT + different literal VALUE: the lookup
    # table is a runtime argument and the conjunct sql renders a
    # value-independent slot, so the SAME executable must serve it
    out2 = run(["p", "q", "r"], [2, 2, 1, 0, None, 1, 1, 2], "q")
    assert out2.columns[0].to_host().to_pylist() == ["q", "q", "q"]
    end = SC.stats()
    assert mid["misses"] > base["misses"]
    assert end["misses"] == mid["misses"], \
        "second dictionary/literal recompiled the fused filter"
    assert end["hits"] > mid["hits"]


def test_final_agg_keys_pass_through_encoded():
    dev = _encoded_device_batch(["x", "y"], [0, 1, 0, 1, 0])
    from spark_rapids_tpu.expressions.base import Alias, BoundReference
    out = ENC.eval_exprs_keep_encoded(
        [Alias(BoundReference(0, T.STRING, True, "s"), "s")], dev)
    assert isinstance(out.columns[0], ENC.DictionaryColumn)


def test_sorted_dictionary_sorts_by_codes_unsorted_falls_back():
    from spark_rapids_tpu.exec.sort import SortSpec, device_sort_batch
    from spark_rapids_tpu.expressions.base import BoundReference
    spec = [SortSpec(BoundReference(0, T.STRING, True, "s"), False,
                     None)]
    # sorted dictionary: codes ARE the order -> no fallback
    s0 = ENC.encoding_stats()
    dev = _encoded_device_batch(["a", "b", "c"], [2, 0, 1, None, 0])
    out = device_sort_batch(dev, spec)
    assert isinstance(out.columns[0], ENC.DictionaryColumn)
    assert out.columns[0].to_host().to_pylist() == \
        ["c", "b", "a", "a", None]
    assert ENC.encoding_stats()["dict_fallbacks"] == s0["dict_fallbacks"]
    # unsorted dictionary: the key column materializes (counted)
    dev2 = _encoded_device_batch(["b", "a", "c"], [0, 1, 2, None])
    out2 = device_sort_batch(dev2, spec)
    assert out2.columns[0].to_host().to_pylist() == ["c", "b", "a", None]
    assert ENC.encoding_stats()["dict_fallbacks"] == \
        s0["dict_fallbacks"] + 1


def test_dictionary_cache_content_addressed():
    v1 = pa.array(["m", "n"])
    v2 = pa.array(["m", "n"])   # distinct arrow object, same content
    assert ENC.Dictionary.of(v1) is ENC.Dictionary.of(v2)
    assert ENC.Dictionary.of(pa.array(["m", "o"])) is not \
        ENC.Dictionary.of(v1)


def test_concat_mismatched_dictionaries_decodes():
    from spark_rapids_tpu.ops.batch_ops import concat_batches
    a = _encoded_device_batch(["x", "y"], [0, 1, 0])
    b = _encoded_device_batch(["y", "x"], [0, 1, 0])
    out = concat_batches([a, b])
    got = sorted(v for v in out.columns[0].to_host().to_pylist())
    assert got == ["x", "x", "x", "y", "y", "y"]
    # matching dictionaries concat in code space
    c = _encoded_device_batch(["x", "y"], [1, 1])
    d = _encoded_device_batch(["x", "y"], [0, None])
    out2 = concat_batches([c, d])
    assert isinstance(out2.columns[0], ENC.DictionaryColumn)
    assert out2.columns[0].to_host().to_pylist() == ["y", "y", "x", None]


# ---------------------------------------------------------------------------
# RLE variant
# ---------------------------------------------------------------------------

def test_rle_upload_roundtrip_and_materialize():
    vals = np.repeat(np.arange(5, dtype=np.int64), 200)
    valid = np.ones(1000, dtype=bool)
    valid[400:600] = False
    hb = HostColumnarBatch(
        [HostColumn.from_numpy(vals, valid, T.LONG)], 1000, ["r"])
    old = ENC.RLE_ENABLED
    ENC.RLE_ENABLED = True
    try:
        dev = upload_host_batch(hb)
    finally:
        ENC.RLE_ENABLED = old
    c = dev.columns[0]
    assert isinstance(c, ENC.RleColumn)
    assert c.runs_bucket < c.bucket, "runs must be smaller than rows"
    got = c.to_host().to_pylist()
    want = [int(v) if ok else None for v, ok in zip(vals, valid)]
    assert got == want
    # sanctioned eager decode agrees
    plain = ENC.materialize(c, site="test")
    assert plain.to_host().to_pylist() == want
    # download materializes runs transparently
    back = download_host_batch(dev)
    assert back.columns[0].to_pylist() == want


def test_rle_query_trimodal(tmp_path):
    n = 3000
    t = pa.table({"d": pa.array(np.repeat(np.arange(3, dtype=np.int64),
                                          n // 3)),
                  "v": pa.array(np.arange(n))})
    path = str(tmp_path / "rle.parquet")
    pq.write_table(t, path)
    extra = {"spark.rapids.sql.encoding.rle.enabled": "true"}
    s0 = ENC.encoding_stats()

    def fn(s):
        return (s.read.parquet(path).filter(col("d") == lit(1))
                .agg(F.sum("v").alias("sv"), F.count("d").alias("c")))
    _assert_trimodal(fn, extra=extra)
    assert ENC.encoding_stats()["rle_columns"] > s0["rle_columns"]


# ---------------------------------------------------------------------------
# compressed spill tier
# ---------------------------------------------------------------------------

def _compressible_host_batch(rows=20_000):
    rng = np.random.default_rng(3)
    return HostColumnarBatch([
        HostColumn.from_numpy(np.repeat(np.arange(rows // 100,
                                                  dtype=np.int64), 100),
                              None, T.LONG),
        HostColumn.from_numpy(rng.integers(0, 4, rows), None, T.LONG),
    ], rows, ["a", "b"])


def test_compressed_spill_roundtrip_under_pressure(tmp_path):
    """Forced host-pool pressure pushes batches to disk through the
    spill codec: round trip is exact and at least 2x the logical bytes
    fit the same on-disk budget."""
    from spark_rapids_tpu.memory import catalog as CAT
    from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier
    hb = _compressible_host_batch()
    logical = hb.nbytes()
    cat = BufferCatalog(device_limit_bytes=1 << 20,
                        host_limit_bytes=logical // 2,  # forces disk
                        disk_dir=str(tmp_path))
    old = CAT.SPILL_CODEC
    CAT.SPILL_CODEC = "lz4"
    try:
        h1 = cat.add_host_batch(hb)
        h2 = cat.add_host_batch(_compressible_host_batch())
        stats = cat.stats()
        assert stats["disk_bytes"] > 0, "pressure must have spilled"
        assert stats["disk_logical_bytes"] >= 2 * stats["disk_bytes"], \
            ("compressed spill must fit >= 2x logical bytes: "
             f"{stats['disk_logical_bytes']} vs {stats['disk_bytes']}")
        spilled = [h for h in (h1, h2)
                   if cat.tier_of(h) == StorageTier.DISK]
        assert spilled
        got = cat.get_host_batch(spilled[0])
        assert got.to_pydict() == hb.to_pydict()
        # accounting: remove() returns every disk byte (recorded size,
        # not a re-stat — satellite fix)
        for h in (h1, h2):
            cat.remove(h)
        stats = cat.stats()
        assert stats["disk_bytes"] == 0
        assert stats["disk_logical_bytes"] == 0
        assert stats["host_bytes"] == 0
    finally:
        CAT.SPILL_CODEC = old
        cat.close()


@pytest.mark.parametrize("codec", ["none", "lz4", "zlib"])
def test_spill_codec_roundtrip(tmp_path, codec):
    from spark_rapids_tpu.memory import catalog as CAT
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    hb = _compressible_host_batch(2000)
    cat = BufferCatalog(device_limit_bytes=1 << 20, host_limit_bytes=1,
                        disk_dir=str(tmp_path))
    old = CAT.SPILL_CODEC
    CAT.SPILL_CODEC = codec
    try:
        h = cat.add_host_batch(hb)
        assert cat.get_host_batch(h).to_pydict() == hb.to_pydict()
    finally:
        CAT.SPILL_CODEC = old
        cat.close()


def test_spill_event_reports_on_disk_and_logical_bytes(tmp_path):
    from spark_rapids_tpu.aux.events import RingBufferSink, add_global_sink, \
        remove_global_sink
    from spark_rapids_tpu.memory import catalog as CAT
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    sink = RingBufferSink(256)
    add_global_sink(sink)
    old = CAT.SPILL_CODEC
    CAT.SPILL_CODEC = "zlib"
    cat = BufferCatalog(device_limit_bytes=1 << 20, host_limit_bytes=1,
                        disk_dir=str(tmp_path))
    try:
        cat.add_host_batch(_compressible_host_batch(5000))
        evs = [e for e in sink.events()
               if e.kind == "spill" and
               e.payload.get("tier") == "host->disk"]
        assert evs
        p = evs[-1].payload
        assert p["codec"] == "zlib"
        assert 0 < p["bytes"] < p["logical_bytes"], \
            "event bytes must be the ACTUAL on-disk (compressed) size"
    finally:
        CAT.SPILL_CODEC = old
        remove_global_sink(sink)
        cat.close()


# ---------------------------------------------------------------------------
# planner pass + conf plumbing
# ---------------------------------------------------------------------------

def test_late_materialization_off_inserts_boundary(enc_parquet):
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    s = tpu_session({"spark.rapids.sql.encoding.lateMaterialization":
                     "false"})
    df = s.read.parquet(enc_parquet).filter(col("s") == lit("beta"))
    final = TpuOverrides(s.conf).apply(df._plan, for_explain=True)
    names = {n.name for n in final.collect_nodes()}
    assert "TpuMaterializeEncodedExec" in names

    def fn(s2):
        return (s2.read.parquet(enc_parquet)
                .filter(col("s") == lit("beta"))
                .agg(F.count("v").alias("c")))
    _assert_trimodal(
        fn, extra={"spark.rapids.sql.encoding.lateMaterialization":
                   "false"})


def test_encoding_disabled_reproduces_plain_plan(enc_parquet):
    """enabled=false: no materialize node, no encoded columns, and the
    plan tree is IDENTICAL to the enabled plan (encoding is a
    representation property, not a plan shape — the one inserted node
    only appears under lateMaterialization=false)."""
    from spark_rapids_tpu.plan.overrides import TpuOverrides

    def plan_of(extra):
        s = tpu_session(extra)
        df = s.read.parquet(enc_parquet).filter(col("s") == lit("beta"))
        return TpuOverrides(s.conf).apply(df._plan, for_explain=True)

    p_on = plan_of(None)
    p_off = plan_of(ENC_OFF)    # last apply wins: module flags now OFF
    assert "TpuMaterializeEncodedExec" not in \
        {n.name for n in p_off.collect_nodes()}
    assert p_off.tree_string() == p_on.tree_string()
    # and the disabled scan genuinely uploads plain columns (the apply
    # above synced the module flags off)
    assert not ENC.ENCODING_ENABLED
    hb = next(iter(
        tpu_session(ENC_OFF).read.parquet(enc_parquet)
        .select("s")._plan.execute_partition(0)))
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    if isinstance(hb, ColumnarBatch):
        assert not ENC.batch_has_encoded(hb)


def test_conf_validation():
    with pytest.raises(ValueError):
        TpuConf({"spark.rapids.sql.encoding.maxDictionarySize": "0"})
    with pytest.raises(ValueError):
        TpuConf({"spark.rapids.memory.spill.codec": "zstdx"})
    TpuConf({"spark.rapids.memory.spill.codec": "none"})


# ---------------------------------------------------------------------------
# AutoTuner rule 8
# ---------------------------------------------------------------------------

def _jline(kind, qid, span, ts, **payload):
    return json.dumps({"event": kind, "query_id": qid, "span_id": span,
                       "ts": ts, "v": 2, **payload})


def _enc_log(tmp_path, n_batches, n_op_fallbacks, n_upload_rejects=0):
    lines = [_jline("queryStart", 4, 1, 1.0, description="enc")]
    t = 1.1
    for _i in range(n_batches):
        lines.append(_jline("encodedBatch", 4, 1, t, dict_columns=1,
                            rle_columns=0, encoded_bytes=4096,
                            decode_avoided_bytes=30000))
        t += 0.01
    for _i in range(n_op_fallbacks):
        lines.append(_jline("encodingFallback", 4, 1, t, site="operator",
                            detail="s", bytes=65536))
        t += 0.01
    for _i in range(n_upload_rejects):
        lines.append(_jline("encodingFallback", 4, 1, t, site="upload",
                            detail="maxDictionarySize", bytes=0,
                            dict_size=1 << 20))
        t += 0.01
    lines.append(_jline("queryEnd", 4, 1, t + 1, duration_s=t))
    log = tmp_path / "enc.jsonl"
    log.write_text("\n".join(lines) + "\n")
    return log


def test_autotune_rule8_fallbacks_dominate(tmp_path):
    from spark_rapids_tpu.tools.autotune import autotune_query
    from spark_rapids_tpu.tools.reader import load_profiles
    profiles, _ = load_profiles(str(_enc_log(tmp_path, 2, 6)))
    recs = autotune_query(profiles[0])
    by_key = {r.key: r for r in recs}
    rec = by_key["spark.rapids.sql.encoding.lateMaterialization"]
    assert rec.recommended is False
    assert any("encodingFallback" in e for e in rec.evidence)


def test_autotune_rule8_oversized_dictionaries(tmp_path):
    from spark_rapids_tpu.tools.autotune import autotune_query
    from spark_rapids_tpu.tools.reader import load_profiles
    profiles, _ = load_profiles(str(_enc_log(tmp_path, 1, 0,
                                             n_upload_rejects=5)))
    recs = autotune_query(profiles[0])
    by_key = {r.key: r for r in recs}
    rec = by_key["spark.rapids.sql.encoding.maxDictionarySize"]
    assert rec.recommended == (1 << 16) // 4
    assert any("dict_size" in e for e in rec.evidence)


def test_autotune_rule8_quiet_on_healthy(tmp_path):
    from spark_rapids_tpu.tools.autotune import autotune_query
    from spark_rapids_tpu.tools.reader import load_profiles
    # one late-mat decode per query is the DESIGN, not a problem
    profiles, _ = load_profiles(str(_enc_log(tmp_path, 8, 1)))
    recs = autotune_query(profiles[0])
    keys = {r.key for r in recs}
    assert not any(k.startswith("spark.rapids.sql.encoding") for k in keys)


def test_profile_reports_decode_avoided_line(tmp_path):
    from spark_rapids_tpu.tools.profile import render_report
    from spark_rapids_tpu.tools.reader import load_profiles
    profiles, diag = load_profiles(str(_enc_log(tmp_path, 3, 1)))
    text = render_report(profiles, diag)
    assert "decodeAvoided=" in text
    assert "encodedBatches=3" in text
    assert "fallbacks=1" in text


# ---------------------------------------------------------------------------
# TPC-DS, encoded vs eager vs CPU
# ---------------------------------------------------------------------------

def _tpcds_trimodal(qname):
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES

    def fn(session):
        register_tables(session, sf=0.02, storage="parquet")
        return session.sql(QUERIES[qname])
    _assert_trimodal(fn, extra={"spark.rapids.sql.test.enabled": "false"})


def test_tpcds_q3_encoded_trimodal():
    _tpcds_trimodal("q3")


def test_tpcds_q7_encoded_trimodal():
    _tpcds_trimodal("q7")


def test_tpcds_q19_encoded_trimodal():
    _tpcds_trimodal("q19")
