"""StageCompiler tier-1 tests (exec/stage_compiler + plan/stages):

- the shared executable cache's hit/miss/evict/trace accounting;
- bounded-LRU eviction;
- zero new traces on the second run of an identical query (the
  ROADMAP-item-1 acceptance assertion);
- literal promotion: one compiled program across differing literals,
  bit-identical results, correct non-promotion of unsafe positions;
- stage fusion on/off bit-identity across TPC-DS tier-1 queries;
- persistent-cache conf wiring, async compile mode, stageCompile
  events, Prometheus counters and AutoTuner rule 7.
"""

import json

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.exec import stage_compiler as SC
from spark_rapids_tpu.expressions.base import Alias, col, lit

from tests.asserts import (assert_tpu_and_cpu_are_equal_collect,
                           cpu_session, tpu_session)

RNG = np.random.default_rng(11)


def _data(n=4000, seed=11):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 50, n).astype(np.int64),
            "w": rng.integers(-100, 100, n).astype(np.int32),
            "v": rng.standard_normal(n)}


def _filter_agg(df, threshold):
    return (df.filter(col("w") > lit(threshold))
            .select(Alias(col("k") + lit(1), "k1"),
                    Alias(col("v"), "v"))
            .agg(F.sum("k1").alias("sk"), F.sum("v").alias("sv")))


# ---------------------------------------------------------------------------
# shared helper semantics
# ---------------------------------------------------------------------------

def test_get_or_build_hit_miss_trace_counters():
    import jax.numpy as jnp
    SC.reset_stats()

    def build():
        def run(x):
            return x + 1
        return run

    key = ("unit", "counters", 1)
    p1 = SC.get_or_build("test.unit", key, build)
    out = p1(jnp.arange(4))
    assert list(np.asarray(out)) == [1, 2, 3, 4]
    p2 = SC.get_or_build("test.unit", key, build)
    assert p2 is p1
    st = SC.stats()
    assert st["misses"] >= 1 and st["hits"] >= 1
    # exactly one trace for one signature, however often it is called
    p1(jnp.arange(4))
    assert SC.stats()["traces_by_kind"]["test.unit"] == 1
    # first dispatch was measured and counted as a compile
    assert st["compiles"] >= 1 and st["compile_s"] >= 0.0


def test_trace_counter_counts_signature_variants():
    import jax.numpy as jnp
    SC.reset_stats()

    def build():
        def run(x):
            return x * 2
        return run

    p = SC.get_or_build("test.variant", ("unit", "variants"), build)
    p(jnp.arange(8))
    p(jnp.arange(8).astype(np.float64))   # new dtype -> genuine retrace
    assert SC.stats()["traces_by_kind"]["test.variant"] == 2


def test_lru_eviction_bounded():
    import jax.numpy as jnp
    SC.clear()
    SC.reset_stats()
    old = SC.stats()["max_programs"]
    try:
        SC.set_max_programs(2)

        def build():
            def run(x):
                return x - 1
            return run

        for i in range(4):
            SC.get_or_build("test.evict", ("unit", "evict", i), build)
        st = SC.stats()
        assert st["programs"] <= 2
        assert st["evictions"] >= 2
        # evicted key rebuilds (miss), resident key hits
        SC.get_or_build("test.evict", ("unit", "evict", 3), build)
        assert SC.stats()["hits"] >= 1
        before = SC.stats()["misses"]
        SC.get_or_build("test.evict", ("unit", "evict", 0), build)
        assert SC.stats()["misses"] == before + 1
    finally:
        SC.set_max_programs(old)


# ---------------------------------------------------------------------------
# zero-retrace steady state (ROADMAP item 1 acceptance)
# ---------------------------------------------------------------------------

def test_second_run_of_identical_query_traces_nothing():
    s = tpu_session()
    df = s.create_dataframe(_data(), num_partitions=2)
    first = _filter_agg(df, 0).collect()
    SC.reset_stats()
    second = _filter_agg(df, 0).collect()
    st = SC.stats()
    assert st["traces"] == 0, \
        f"second identical run retraced: {st['traces_by_kind']}"
    assert st["misses"] == 0 and st["hits"] > 0
    assert first == second


def test_second_run_tpcds_query_traces_nothing():
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    register_tables(s, sf=0.02)
    first = s.sql(QUERIES["q3"]).collect()
    SC.reset_stats()
    second = s.sql(QUERIES["q3"]).collect()
    st = SC.stats()
    assert st["traces"] == 0, \
        f"q3 second run retraced: {st['traces_by_kind']}"
    assert sorted(map(str, first)) == sorted(map(str, second))


# ---------------------------------------------------------------------------
# literal promotion
# ---------------------------------------------------------------------------

def test_promotion_unit_placeholders_and_slots():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions.base import BoundReference, Literal
    from spark_rapids_tpu.expressions.predicates import GreaterThan
    from spark_rapids_tpu.expressions.arithmetic import Add
    from spark_rapids_tpu.plan.stages import (PromotedLiteral,
                                              promote_stage_literals)
    w = BoundReference(0, T.INT, True, "w")
    v = BoundReference(1, T.DOUBLE, True, "v")
    ops = [("filter", GreaterThan(w, Literal(5, T.INT))),
           ("project", [Add(v, Literal(1.5, T.DOUBLE)),
                        # dtype mismatch (INT col vs LONG literal): kept
                        GreaterThan(w, Literal(7, T.LONG)),
                        # a bare string column has no literal to promote
                        BoundReference(2, T.STRING, True, "s")])]
    new_ops, promoted = promote_stage_literals(ops)
    assert len(promoted) == 2
    assert [p.value for p in promoted] == [5, 1.5]
    assert "$lit0" in new_ops[0][1].sql()
    assert "$lit1" in new_ops[1][1][0].sql()
    assert "7" in new_ops[1][1][1].sql()          # mismatch: untouched
    assert isinstance(promoted[0], PromotedLiteral)
    # original tree untouched (plans are shared)
    assert "5" in ops[0][1].sql()


def test_promotion_unit_strings():
    """A string literal beside a per-row string is promoted under every
    comparison; beside a literal, under arithmetic's parents or longer
    than the width it travels in, it is not."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expressions.base import BoundReference, Literal
    from spark_rapids_tpu.expressions import predicates as P
    from spark_rapids_tpu.plan.stages import (PROMOTED_STRING_WIDTH,
                                              PromotedLiteral,
                                              physical_literal,
                                              promote_stage_literals)
    s = BoundReference(0, T.STRING, True, "s")
    comparisons = (P.EqualTo, P.NotEqual, P.LessThan, P.LessThanOrEqual,
                   P.GreaterThan, P.GreaterThanOrEqual, P.EqualNullSafe)
    ops = [("filter", cls(s, Literal("M", T.STRING)))
           for cls in comparisons]
    ops.append(("filter", P.EqualTo(Literal("Primary", T.STRING), s)))
    new_ops, promoted = promote_stage_literals(ops)
    assert [p.value for p in promoted] == ["M"] * 7 + ["Primary"]
    assert all(isinstance(p, PromotedLiteral) for p in promoted)
    assert new_ops[0][1].sql() == "(s = $lit0:string)"
    assert new_ops[7][1].sql() == "($lit7:string = s)"
    # the value, not the slot, is what the CPU oracle and explain read
    assert promoted[7].eval_cpu(None).data == "Primary"
    # one shape for every value: the program's key cannot follow it
    for value in ("M", "Advanced Degree", "é"):
        chars, length = physical_literal(value, T.STRING)
        assert chars.shape == (PROMOTED_STRING_WIDTH,)
        assert chars.dtype == np.uint8 and length.dtype == np.int32
        assert bytes(chars[:int(length)]).decode() == value
    kept = [("filter", P.EqualTo(Literal("a", T.STRING),
                                 Literal("b", T.STRING))),
            ("filter", P.EqualTo(s, Literal(None, T.STRING))),
            ("filter", P.EqualTo(s, Literal(
                "x" * (PROMOTED_STRING_WIDTH + 1), T.STRING)))]
    _, promoted = promote_stage_literals(kept)
    assert promoted == []


def _string_data():
    rng = np.random.default_rng(5)
    values = ["M", "F", "Advanced Degree", "Advanced", "Primary", "",
              "Advanced Degrees", None]
    return {"s": [values[i] for i in rng.integers(0, len(values), 3000)],
            "x": rng.integers(0, 9, 3000).astype(np.int64)}


def _string_filter(df, op, value):
    cond = {"=": lambda c, v: c == v, "<": lambda c, v: c < v,
            ">=": lambda c, v: c >= v,
            "<>": lambda c, v: c != v}[op](col("s"), lit(value))
    return df.filter(cond).agg(F.sum("x").alias("sx"),
                               F.count("x").alias("cx"))


@pytest.mark.parametrize("op", ["=", "<", ">=", "<>"])
def test_promoted_string_literals_share_one_program(op):
    """'M', then 'F', then 'Advanced Degree' (the column's width), then a
    literal wider than the column: no trace after the first, and every
    answer is the CPU engine's, nulls and the empty string included."""
    data = _string_data()
    s = tpu_session()
    df = s.create_dataframe(data, num_partitions=1)
    cdf = cpu_session().create_dataframe(data, num_partitions=1)
    first = _string_filter(df, op, "M").collect()
    assert "lits[$lit0='M']" in _string_filter(df, op, "M").explain()
    SC.reset_stats()
    got = {"M": first}
    for value in ("F", "Advanced Degree", "Advanced Degree and more", ""):
        got[value] = _string_filter(df, op, value).collect()
    st = SC.stats()
    assert st["traces"] == 0, \
        f"a new string literal retraced: {st['traces_by_kind']}"
    for value, rows in got.items():
        want = _string_filter(cdf, op, value).collect()
        assert [tuple(r.values()) for r in rows] == \
            [tuple(r.values()) for r in want], (op, value)
    assert got["M"] != got["F"]            # the values bind


def test_promoted_string_null_safe_equal_and_literal_first():
    data = _string_data()

    def fn(value):
        def run(session):
            from spark_rapids_tpu.expressions.predicates import (
                EqualNullSafe, LessThan)
            df = session.create_dataframe(data, num_partitions=1)
            return (df.filter(EqualNullSafe(col("s"), lit(value)))
                      .filter(LessThan(lit("A"), col("s")))
                      .agg(F.count("x").alias("cx")))
        return run
    assert_tpu_and_cpu_are_equal_collect(fn("M"))
    assert_tpu_and_cpu_are_equal_collect(fn("Advanced Degree"))


@pytest.mark.parametrize("op", ["=", "<"])
def test_a_string_literal_over_the_promoted_width_stays_baked(op):
    """A literal longer than ``PROMOTED_STRING_WIDTH`` is the one kind a
    comparison still bakes into its program: explain shows no slot for it,
    and the answer is the CPU engine's."""
    from spark_rapids_tpu.plan.stages import PROMOTED_STRING_WIDTH
    data = _string_data()
    wide = "Advanced Degree" + "s" * PROMOTED_STRING_WIDTH
    df = tpu_session().create_dataframe(data, num_partitions=1)
    assert "$lit0" not in _string_filter(df, op, wide).explain()
    got = _string_filter(df, op, wide).collect()
    want = _string_filter(cpu_session().create_dataframe(
        data, num_partitions=1), op, wide).collect()
    assert [tuple(r.values()) for r in got] == \
        [tuple(r.values()) for r in want]
    assert (got[0]["cx"] == 0) == (op == "=")   # equals none, follows some


def test_promoted_literals_share_one_program_across_values():
    s = tpu_session()
    df = s.create_dataframe(_data(), num_partitions=1)
    r0 = _filter_agg(df, 0).collect()      # compiles the stage
    SC.reset_stats()
    r5 = _filter_agg(df, 5).collect()      # same shape, new literal
    st = SC.stats()
    assert st["traces"] == 0, \
        f"literal change recompiled: {st['traces_by_kind']}"
    assert r0 != r5                        # and the VALUES actually bind
    # oracle: both thresholds match the CPU engine bit-for-bit
    for thr, rows in ((0, r0), (5, r5)):
        c = _filter_agg(cpu_session().create_dataframe(
            _data(), num_partitions=1), thr).collect()
        assert abs(c[0]["sk"] - rows[0]["sk"]) == 0
        assert abs(c[0]["sv"] - rows[0]["sv"]) <= 1e-9 * abs(c[0]["sv"])


def test_promotion_disabled_still_correct():
    def fn(session):
        df = session.create_dataframe(_data(), num_partitions=2)
        return _filter_agg(df, 3)
    assert_tpu_and_cpu_are_equal_collect(
        fn, conf={"spark.rapids.sql.compile.literalPromotion": "false"})


def test_promoted_date_literals(tmp_path):
    import datetime
    rng = np.random.default_rng(3)
    days = rng.integers(10_000, 11_000, 1000)
    data = {"d": [datetime.date(1970, 1, 1) + datetime.timedelta(days=int(x))
                  for x in days],
            "x": rng.integers(0, 9, 1000).astype(np.int64)}

    def fn(session):
        df = session.create_dataframe(data, num_partitions=1)
        return (df.filter(col("d") >= lit(datetime.date(1998, 1, 1)))
                  .agg(F.sum("x").alias("sx"), F.count("x").alias("cx")))
    assert_tpu_and_cpu_are_equal_collect(fn)


def test_promoted_literal_inside_lambda_body():
    """Regression: a promoted literal inside a higher-order function's
    lambda body must bind through the lambda's derived EvalContext.  The
    compiled program is cached under a value-independent key, so a
    dropped literal_args binding would bake the FIRST query's constant
    into a program the second query shares — silent wrong results."""
    from spark_rapids_tpu import types as T
    rng = np.random.default_rng(7)
    data = {"a": [[int(v) for v in rng.integers(-9, 9, 1 + i % 4)]
                  for i in range(500)],
            "k": np.arange(500, dtype=np.int64)}
    schema = T.StructType([T.StructField("a", T.ArrayType(T.LONG)),
                           T.StructField("k", T.LONG)])

    def fn(mult):
        def run(session):
            df = session.create_dataframe(data, schema=schema,
                                          num_partitions=1)
            return (df.filter(col("k") >= lit(np.int64(0)))
                      .select(Alias(F.transform(
                          col("a"), lambda x: x * lit(np.int64(mult))),
                          "t"),
                          Alias(col("k"), "k")))
        return run

    # same plan shape, different lambda literal: the second query hits
    # the first's cached program and must still multiply by ITS value
    assert_tpu_and_cpu_are_equal_collect(fn(2))
    assert_tpu_and_cpu_are_equal_collect(fn(3))


def test_literal_vs_literal_comparison_not_promoted():
    """Regression: pure-constant subtrees (lit op lit) must NOT have
    their literals promoted to traced runtime args — the scalar-scalar
    eval branches run python-level ops (bool()/np.asarray()) that crash
    on a tracer.  Constant math stays baked into the program."""
    def fn(session):
        df = session.create_dataframe(_data(), num_partitions=1)
        return (df.filter(col("w") > lit(np.int32(5)) - lit(np.int32(2)))
                  .agg(F.sum("v").alias("sv"), F.count("w").alias("cw")))
    assert_tpu_and_cpu_are_equal_collect(fn)


# ---------------------------------------------------------------------------
# stage fusion on/off bit-identity over TPC-DS
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", [
    "q3",
    # q3 stays in tier-1 (cheap, covers filter+join+agg fusion);
    # the wider sweep is slow-only — fusion is default-on, so every
    # tier-1 TPC-DS vs-CPU test already executes through the compiler
    pytest.param("q1", marks=pytest.mark.slow),
    pytest.param("q7", marks=pytest.mark.slow),
    pytest.param("q15", marks=pytest.mark.slow),
    pytest.param("q19", marks=pytest.mark.slow),
])
def test_tpcds_fused_vs_per_operator_bit_identical(qname):
    """The stage compiler must be invisible to results: the same TPC-DS
    query through fused stages and through per-operator dispatch returns
    identical row sets (each side is separately compared against the CPU
    engine by tests/tpcds_differential.py; this pins the fusion pass itself)."""
    from spark_rapids_tpu.testing.rowcompare import rows_equal
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES

    def run(extra):
        conf = {"spark.rapids.sql.test.enabled": "false"}
        conf.update(extra)
        s = tpu_session(conf)
        register_tables(s, sf=0.02)
        return s.sql(QUERIES[qname]).collect()

    fused = run({})
    unfused = run({"spark.rapids.sql.compile.stageFusion.enabled":
                   "false"})
    diff = rows_equal(unfused, fused, check_order=False, approx_float=True)
    assert diff is None, diff


def test_fusion_disabled_drops_fused_nodes():
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    s = tpu_session({"spark.rapids.sql.compile.stageFusion.enabled":
                     "false"})
    df = s.create_dataframe(_data(), num_partitions=1)
    q = df.filter(col("w") > lit(0)).select(Alias(col("k") + lit(1), "k1"))
    plan = TpuOverrides(s.conf).apply(q._plan, for_explain=True)
    names = {n.name for n in plan.collect_nodes()}
    assert not any(n.startswith("TpuFused") for n in names), names


# ---------------------------------------------------------------------------
# cache-key correctness: schema / bucket changes compile separate programs
# ---------------------------------------------------------------------------

def test_different_schema_and_bucket_get_their_own_programs():
    s = tpu_session()
    df1 = s.create_dataframe(_data(1500, seed=1), num_partitions=1)
    _filter_agg(df1, 0).collect()
    SC.reset_stats()
    # different row bucket (forces new shapes end to end)
    df2 = s.create_dataframe(_data(700, seed=2), num_partitions=1)
    r2 = _filter_agg(df2, 0).collect()
    assert SC.stats()["misses"] > 0
    c = _filter_agg(cpu_session().create_dataframe(
        _data(700, seed=2), num_partitions=1), 0).collect()
    assert abs(c[0]["sk"] - r2[0]["sk"]) == 0


# ---------------------------------------------------------------------------
# tier 2 (persistent disk cache) + async compile
# ---------------------------------------------------------------------------

def test_persistent_cache_dir_stands_aside_for_the_environment(
        tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR places the cache, JAX reads the
    variable itself: the conf assigns nothing."""
    import jax
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    try:
        SC.set_persistent_cache_dir(str(tmp_path / "from-conf"))
        assert jax.config.jax_compilation_cache_dir == before
        assert SC.stats()["disk_cache_dir"] == placed
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        SC.set_persistent_cache_dir("")
    assert SC.stats()["disk_cache_dir"] is None


def test_persistent_cache_dir_conf(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = str(tmp_path / "xla-cache")
    s = tpu_session({"spark.rapids.sql.compile.cacheDir": d})
    try:
        df = s.create_dataframe(_data(800, seed=5), num_partitions=1)
        _filter_agg(df, 1).collect()
        st = SC.stats()
        assert st["disk_cache_dir"] == d
        assert st["disk_cache_error"] is None
    finally:
        SC.set_persistent_cache_dir("")
    assert SC.stats()["disk_cache_dir"] is None


def test_async_compile_bit_identical_and_warms():
    SC.clear()     # force fresh programs so the warm path actually runs

    def fn(session):
        # filter+select WITHOUT an aggregate: fuses to TpuFusedStageExec,
        # the exec that runs the async look-ahead
        df = session.create_dataframe(_data(2000, seed=7),
                                      num_partitions=2)
        return (df.filter(col("w") > lit(-5))
                  .select(Alias(col("k") * lit(3), "k3")))
    SC.reset_stats()
    assert_tpu_and_cpu_are_equal_collect(
        fn, conf={"spark.rapids.sql.compile.async": "true"})
    assert SC.stats()["async_compiles"] >= 1
    # the flag is session-scoped: the next default-conf action resets it
    s = tpu_session()
    s.create_dataframe({"z": np.arange(8)}, num_partitions=1).collect()
    assert SC.ASYNC_COMPILE is False


# ---------------------------------------------------------------------------
# observability: events, Prometheus, AutoTuner rule 7
# ---------------------------------------------------------------------------

def test_stage_compile_events_logged(tmp_path):
    log = tmp_path / "ev.jsonl"
    s = tpu_session({"spark.rapids.sql.eventLog.path": str(log)})
    # a unique row count -> unique bucket-independent shape is not
    # guaranteed, so force novelty through a fresh column layout
    rng = np.random.default_rng(17)
    df = s.create_dataframe(
        {"a1": rng.integers(0, 5, 900).astype(np.int16),
         "b1": rng.standard_normal(900).astype(np.float32)},
        num_partitions=1)
    (df.filter(col("a1") > lit(np.int16(1)))
       .agg(F.count("b1").alias("c"))).collect()
    evs = [json.loads(l) for l in log.read_text().splitlines()
           if '"stageCompile"' in l]
    assert evs, "no stageCompile events reached the event log"
    for e in evs:
        assert e["event"] == "stageCompile"
        assert e["duration_s"] >= 0.0
        assert e["tier"] in ("jit", "aot")
        assert e["stage_kind"]


def test_render_prometheus_stage_counters():
    from spark_rapids_tpu.aux.events import render_prometheus
    text = render_prometheus()
    for name in ("spark_rapids_tpu_stage_programs",
                 "spark_rapids_tpu_stage_cache_hits_total",
                 "spark_rapids_tpu_stage_cache_misses_total",
                 "spark_rapids_tpu_stage_cache_evictions_total",
                 "spark_rapids_tpu_stage_traces_total",
                 "spark_rapids_tpu_stage_compile_seconds_total"):
        assert name in text


def test_profile_compile_bucket(tmp_path):
    from spark_rapids_tpu.tools.profile import attribute
    from spark_rapids_tpu.tools.reader import load_profiles
    log = tmp_path / "prof.jsonl"
    lines = [
        json.dumps({"event": "queryStart", "query_id": 3, "span_id": 1,
                    "ts": 1.0, "v": 2, "description": "q", "conf": {}}),
        json.dumps({"event": "stageCompile", "query_id": 3, "span_id": 2,
                    "ts": 1.5, "v": 2, "stage_kind": "fused.stage",
                    "key": "abc", "duration_s": 2.0, "tier": "jit",
                    "disk_cache": False}),
        json.dumps({"event": "queryEnd", "query_id": 3, "span_id": 1,
                    "ts": 5.0, "v": 2, "duration_s": 4.0,
                    "semaphore_wait_s": 0.0, "events_dropped": 0}),
    ]
    log.write_text("\n".join(lines) + "\n")
    profiles, _ = load_profiles(str(log))
    att = attribute(profiles[0])
    assert att.raw["compile"] == 2.0
    assert att.scaled["compile"] > 0.0


def test_autotune_cold_compile_rule(tmp_path):
    from spark_rapids_tpu.tools.autotune import autotune_query
    from spark_rapids_tpu.tools.reader import load_profiles
    log = tmp_path / "cold.jsonl"
    lines = [json.dumps({"event": "queryStart", "query_id": 9,
                         "span_id": 1, "ts": 0.0, "v": 2,
                         "description": "cold", "conf": {}})]
    for i in range(9):
        lines.append(json.dumps(
            {"event": "stageCompile", "query_id": 9, "span_id": 2 + i,
             "ts": 0.1 * i, "v": 2, "stage_kind": f"fused.k{i}",
             "key": f"h{i}", "duration_s": 0.5, "tier": "jit",
             "disk_cache": False}))
    lines.append(json.dumps({"event": "queryEnd", "query_id": 9,
                             "span_id": 1, "ts": 6.0, "v": 2,
                             "duration_s": 6.0, "semaphore_wait_s": 0.0,
                             "events_dropped": 0}))
    log.write_text("\n".join(lines) + "\n")
    profiles, _ = load_profiles(str(log))
    recs = autotune_query(profiles[0])
    by_key = {r.key: r for r in recs}
    rec = by_key.get("spark.rapids.sql.compile.cacheDir")
    assert rec is not None, [r.key for r in recs]
    assert rec.evidence and any("stageCompile" in e for e in rec.evidence)
    # with the disk tier already on, the same events keep the rule silent
    warm = [json.loads(l) for l in lines]
    for e in warm:
        if e["event"] == "stageCompile":
            e["disk_cache"] = True
    warm_log = tmp_path / "warm.jsonl"
    warm_log.write_text("\n".join(json.dumps(e) for e in warm) + "\n")
    warm_recs = autotune_query(load_profiles(str(warm_log))[0][0])
    assert "spark.rapids.sql.compile.cacheDir" not in \
        {r.key for r in warm_recs}


# ---------------------------------------------------------------------------
# conf validation
# ---------------------------------------------------------------------------

def test_compile_conf_validation():
    from spark_rapids_tpu.config import TpuConf
    with pytest.raises(ValueError):
        TpuConf({"spark.rapids.sql.compile.maxPrograms": "0"})
    with pytest.raises(ValueError):
        TpuConf({"spark.rapids.sql.compile.async": "maybe"})
    c = TpuConf({"spark.rapids.sql.compile.maxPrograms": "64",
                 "spark.rapids.sql.compile.cacheDir": "/tmp/x",
                 "spark.rapids.sql.compile.async": "true",
                 "spark.rapids.sql.compile.literalPromotion": "false",
                 "spark.rapids.sql.compile.stageFusion.enabled": "false"})
    assert c.get("spark.rapids.sql.compile.maxPrograms") == 64
