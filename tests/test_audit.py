"""Compiled-program auditor + plan-invariant verifier tests (ISSUE 12):

- the tier-1 gate in the test_lint.py repo-is-clean style: a
  representative workload (promoted-literal fused stages + TPC-DS q3)
  audits clean — zero forbidden primitives, zero baked-constant errors,
  a populated roofline table;
- a deliberately regressed fixture (literal promotion disabled) is
  flagged as a recompile storm, and the AutoTuner's rule 9 recommends
  the promotion conf from the same evidence;
- golden program-structure regression: a second identical TPC-DS q3 run
  adds zero ledger rows and keeps the structural-signature set stable
  (cache-key explosions the zero-retrace test cannot see);
- ledger hygiene: no live device references reachable from audit state
  after stage_compiler.clear(), and rows survive event-log gzip+rotation
  through tools/reader;
- the runtime plan-invariant verifier: clean across TPC-DS smoke
  queries when armed, and hand-broken plans (materialize boundary
  removed, stacked spools, exchange split apart) are caught with
  planInvariantViolation events.
"""

import gc
import json
import os
import subprocess
import sys
import weakref

import numpy as np

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.exec import stage_compiler as SC
from spark_rapids_tpu.expressions.base import Alias, col, lit
from spark_rapids_tpu.plan import verify as PV
from spark_rapids_tpu.tools.audit import (LedgerRow, cluster_rows,
                                          load_ledger, render_audit,
                                          run_audit, write_audit_baseline)

from tests.asserts import tpu_session

RNG = np.random.default_rng(12)
# w is int32 so `col("w") > lit(threshold)` is a same-dtype comparison —
# the promotable-literal pattern (plan/stages.py promotes only same-dtype
# operands; an int64 column would make the thresholds bake per value)
_DATA = {"k": RNG.integers(0, 50, 30000).astype(np.int64),
         "w": RNG.integers(-100, 100, 30000).astype(np.int32),
         "v": RNG.standard_normal(30000)}


def _filter_agg(df, threshold):
    return (df.filter(col("w") > lit(threshold))
            .select(Alias(col("k") + lit(1), "k1"), Alias(col("v"), "v"))
            .agg(F.sum("k1").alias("sk"), F.sum("v").alias("sv")))


def _logged_session(log, **extra):
    conf = {"spark.rapids.sql.test.enabled": "false",
            "spark.rapids.sql.eventLog.path": str(log)}
    conf.update(extra)
    return tpu_session(conf)


# ---------------------------------------------------------------------------
# the repo gate (acceptance criteria)
# ---------------------------------------------------------------------------

def test_repo_workload_audits_clean(tmp_path):
    """THE acceptance gate: a promoted-literal workload's ledger has
    zero forbidden primitives, zero baked-constant errors, and a
    per-program roofline table."""
    log = tmp_path / "clean.jsonl"
    s = _logged_session(log)
    SC.clear()
    SC.reset_stats()
    df = s.create_dataframe(_DATA, num_partitions=2)
    results = [_filter_agg(df, t).collect() for t in (0, 10, 20, 30)]
    assert all(results)
    st = SC.stats()
    assert st["ledger_rows"] > 0, "ledger recorded nothing"
    assert st["ledger_errors"] == 0, "ledger recording failed"
    report = run_audit(str(log))
    assert len(report.rows) == st["ledger_rows"]
    msgs = [f"{f.pass_id}: {f.message}" for f in report.active
            if f.pass_id in ("forbidden-primitive", "baked-constant",
                             "recompile-storm")]
    assert not msgs, "audit findings on the repo workload:\n" + \
        "\n".join(msgs)
    assert report.exit_code == 0
    # promoted literals: the four thresholds shared executables, so no
    # structure carries more than one cache key for the fused stages
    clusters = cluster_rows(report.rows)
    fused = {ck: by_key for ck, by_key in clusters.items()
             if ck[0].startswith("fused.")}
    assert fused, "workload built no fused-stage programs"
    assert all(len(by_key) == 1 for by_key in fused.values()), \
        {ck: sorted(bk) for ck, bk in fused.items() if len(bk) > 1}
    # the roofline table exists and carries flops/bytes verdicts
    assert report.roofline
    assert any(e.flops is not None and e.bound in ("compute", "memory")
               for e in report.roofline)
    text = render_audit(report)
    assert "Roofline" in text and text.rstrip().endswith("OK")


def test_regressed_fixture_flags_recompile_storm(tmp_path):
    """Literal promotion disabled is the deliberately regressed engine:
    per-value cache keys over one program structure = a storm."""
    log = tmp_path / "storm.jsonl"
    s = _logged_session(
        log, **{"spark.rapids.sql.compile.literalPromotion": "false"})
    SC.clear()
    SC.reset_stats()
    df = s.create_dataframe(_DATA, num_partitions=2)
    for t in (0, 10, 20, 30):
        _filter_agg(df, t).collect()
    report = run_audit(str(log))
    storms = [f for f in report.active if f.pass_id == "recompile-storm"]
    assert storms, "promotion-off per-value keys must read as a storm"
    assert any("literal" in f.message for f in storms), \
        [f.message for f in storms]
    assert report.exit_code == 1
    # the regression is invisible to the trace counter on a repeat run
    # (each value's program is cached!) — only the ledger sees it
    SC.reset_stats()
    _filter_agg(df, 20).collect()
    assert SC.stats()["traces"] == 0


def test_autotune_rule9_recommends_promotion(tmp_path):
    from spark_rapids_tpu.tools.autotune import autotune
    from spark_rapids_tpu.tools.reader import load_profiles
    log = tmp_path / "storm9.jsonl"
    s = _logged_session(
        log, **{"spark.rapids.sql.compile.literalPromotion": "false"})
    SC.clear()
    df = s.create_dataframe(_DATA, num_partitions=2)
    for t in (0, 10, 20, 30):
        _filter_agg(df, t).collect()
    profiles, _ = load_profiles(str(log))
    recs = autotune(profiles)
    (rec,) = [r for r in recs
              if r.key == "spark.rapids.sql.compile.literalPromotion"]
    assert rec.recommended is True
    assert rec.evidence and "stageProgram" in rec.evidence[1]
    # quiet on the healthy (promotion-on) log
    log2 = tmp_path / "healthy9.jsonl"
    s2 = _logged_session(log2)
    SC.clear()
    df2 = s2.create_dataframe(_DATA, num_partitions=2)
    for t in (0, 10, 20, 30):
        _filter_agg(df2, t).collect()
    profiles2, _ = load_profiles(str(log2))
    assert not [r for r in autotune(profiles2)
                if r.key == "spark.rapids.sql.compile.literalPromotion"]


# ---------------------------------------------------------------------------
# golden program-structure regression (TPC-DS q3)
# ---------------------------------------------------------------------------

def test_q3_second_run_stable_structural_signatures(tmp_path):
    """A second identical q3 run adds ZERO ledger rows and keeps the
    structural-signature set stable — the cache-key-explosion guard the
    zero-retrace test cannot provide (a per-value key explosion traces
    nothing on repeats: every value's program is warm)."""
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    log = tmp_path / "q3.jsonl"
    s = _logged_session(log)
    register_tables(s, sf=0.02)
    SC.clear()
    SC.reset_stats()
    first = s.sql(QUERIES["q3"]).collect()
    rows1, _profiles, _diag, _pv = load_ledger(str(log))
    assert rows1, "q3 built no programs into the ledger"
    sigs1 = {(r.kind, r.norm_sig) for r in rows1}
    second = s.sql(QUERIES["q3"]).collect()
    rows2, _profiles, _diag, _pv = load_ledger(str(log))
    assert len(rows2) == len(rows1), (
        f"q3 re-run built {len(rows2) - len(rows1)} new program(s): "
        "cache keys discriminate on something that varies per run")
    assert {(r.kind, r.norm_sig) for r in rows2} == sigs1
    assert sorted(map(str, first)) == sorted(map(str, second))
    # and the audit over the q3 ledger is clean of error findings
    report = run_audit(rows=rows2, profiles=None)
    assert not report.active_errors, \
        [f.message for f in report.active_errors]


# ---------------------------------------------------------------------------
# pass unit fixtures
# ---------------------------------------------------------------------------

def test_forbidden_primitive_detected():
    """A real program with a host callback lands in the ledger with the
    callback primitive and the audit flags it."""
    import jax
    import jax.numpy as jnp
    ring = EV.RingBufferSink()
    EV.add_global_sink(ring)
    try:
        SC.reset_stats()

        def build():
            def run(x):
                y = jax.pure_callback(
                    lambda v: np.asarray(v) * 2.0,  # lint: ok=traced-purity -- fixture: the forbidden pattern itself
                    jax.ShapeDtypeStruct(x.shape, x.dtype), x)
                return y.sum()
            return run

        p = SC.get_or_build("test.audit.callback", ("cb", 1), build)
        p(jnp.arange(8.0))
        rows = [LedgerRow.from_event(e) for e in ring.events()
                if e.kind == "stageProgram"]
        assert rows and "pure_callback" in rows[-1].primitives
        report = run_audit(rows=rows)
        bad = [f for f in report.active
               if f.pass_id == "forbidden-primitive"]
        assert bad and "pure_callback" in bad[0].message
        assert report.exit_code == 1
    finally:
        EV.remove_global_sink(ring)


def test_baked_constant_variance_detected():
    """Two programs sharing one structure whose baked const differs by
    key = the missed table-promotion bug class."""
    import jax.numpy as jnp
    ring = EV.RingBufferSink()
    EV.add_global_sink(ring)
    try:
        for i in range(2):
            table = np.arange(64.0) + i     # differs per key

            def build(table=table):
                def run(x):
                    return (x + table).sum()
                return run

            SC.get_or_build("test.audit.baked", ("t", i),
                            build)(jnp.ones(64))
        rows = [LedgerRow.from_event(e) for e in ring.events()
                if e.kind == "stageProgram"]
        assert len(rows) == 2
        assert rows[0].norm_sig == rows[1].norm_sig
        assert rows[0].consts[0]["fp"] != rows[1].consts[0]["fp"]
        report = run_audit(rows=rows)
        baked = [f for f in report.active
                 if f.pass_id == "baked-constant"]
        assert baked and baked[0].severity == "error"
        assert "promotion" in baked[0].message
    finally:
        EV.remove_global_sink(ring)


def test_dtype_audit_flags_silent_widening():
    row = LedgerRow(
        kind="test.widen", key="k1", key_repr="()", struct_sig="s",
        norm_sig="n", primitives=["convert_element_type"], eqns=1,
        consts=[], n_args=1, args=["float32[8]"],
        in_dtypes=["float32"], out_dtypes=["float64"],
        flops=1.0, bytes_accessed=8.0)
    report = run_audit(rows=[row])
    (f,) = [f for f in report.active if f.pass_id == "dtype-audit"]
    assert f.severity == "warning" and "float64" in f.message
    # warnings alone never fail the audit
    assert report.exit_code == 0


def test_roofline_flags_below_floor():
    row = LedgerRow(
        kind="fused.stage", key="k1", key_repr="()", struct_sig="s",
        norm_sig="n", primitives=["add"], eqns=1, consts=[], n_args=1,
        args=["float32[1024]"], in_dtypes=["float32"],
        out_dtypes=["float32"], flops=1024.0, bytes_accessed=8192.0)

    import spark_rapids_tpu.tools.audit.passes as AP
    orig = AP._measured_by_kind
    # one measured second for one dispatch of the fused.stage kind
    AP._measured_by_kind = lambda profiles: {"fused.stage": (1.0, 1)}
    try:
        report = run_audit(rows=[row], profiles=[object()],
                           min_peak_fraction=0.5)
    finally:
        AP._measured_by_kind = orig
    (e,) = report.roofline
    assert e.bound == "memory" and e.sec_per_call == 1.0
    assert e.peak_fraction is not None and e.peak_fraction < 0.5
    (f,) = [f for f in report.active if f.pass_id == "roofline"]
    assert f.severity == "warning"


def test_audit_baseline_suppresses(tmp_path):
    row_a = LedgerRow(
        kind="test.base", key="ka", key_repr="a", struct_sig="sa",
        norm_sig="n1", primitives=["pure_callback"], eqns=1, consts=[],
        n_args=0, args=[], in_dtypes=[], out_dtypes=[], flops=None,
        bytes_accessed=None)
    report = run_audit(rows=[row_a])
    assert report.exit_code == 1
    base = tmp_path / "audit-base.json"
    n = write_audit_baseline(str(base), report)
    assert n == 1
    report2 = run_audit(rows=[row_a], baseline_path=str(base))
    assert report2.exit_code == 0
    assert [f.suppressed for f in report2.findings] == ["baseline"]
    # idempotent re-write: a second --write-baseline over the same log
    # must keep the grandfathered entries, not wipe them
    assert write_audit_baseline(str(base), report2) == 1
    report2b = run_audit(rows=[row_a], baseline_path=str(base))
    assert report2b.exit_code == 0
    # a new structure is NOT grandfathered
    row_b = LedgerRow(
        kind="test.base", key="kb", key_repr="b", struct_sig="sb",
        norm_sig="n2", primitives=["io_callback"], eqns=1, consts=[],
        n_args=0, args=[], in_dtypes=[], out_dtypes=[], flops=None,
        bytes_accessed=None)
    report3 = run_audit(rows=[row_a, row_b], baseline_path=str(base))
    assert report3.exit_code == 1
    assert len(report3.active_errors) == 1


def test_cli_audit_subcommand(tmp_path):
    log = tmp_path / "cli.jsonl"
    s = _logged_session(log)
    SC.clear()
    df = s.create_dataframe(_DATA, num_partitions=2)
    _filter_agg(df, 5).collect()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "audit",
         str(log), "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    d = json.loads(out.stdout)
    assert d["programs"] > 0 and d["summary"]["active_errors"] == 0
    assert d["roofline"]
    json.loads(json.dumps(d))


# ---------------------------------------------------------------------------
# ledger hygiene
# ---------------------------------------------------------------------------

def test_ledger_holds_no_device_references(tmp_path):
    """stage_compiler.clear() after a ledger-recording run leaves no
    live jax arrays reachable from audit state: const fingerprints are
    hashes, never buffers."""
    import jax.numpy as jnp
    log = tmp_path / "devref.jsonl"
    sink = EV.JsonlEventLogSink(str(log))
    EV.add_global_sink(sink)
    try:
        SC.reset_stats()
        table = jnp.arange(256.0) * 3.0
        ref = weakref.ref(table)

        def build():
            def run(x):
                return (x * table).sum()
            return run

        p = SC.get_or_build("test.audit.devref", ("devref", 1), build)
        assert float(p(jnp.ones(256))) == float((jnp.arange(256.0)
                                                 * 3.0).sum())
        assert SC.stats()["ledger_rows"] >= 1
        del p, build, table
        SC.clear()
        gc.collect()
        assert ref() is None, \
            "a device const stayed reachable after clear()"
    finally:
        EV.remove_global_sink(sink)
        sink.close()


def test_ledger_rows_survive_gzip_rotation(tmp_path):
    """stageProgram rows round-trip through rotated, gzip'd event logs
    via tools/reader (schema v3 in the header)."""
    log = tmp_path / "rot.jsonl"
    s = _logged_session(
        log, **{"spark.rapids.sql.eventLog.maxBytes": "1024",
                "spark.rapids.sql.eventLog.compress": "true"})
    SC.clear()
    df = s.create_dataframe(_DATA, num_partitions=2)
    _filter_agg(df, 7).collect()
    _filter_agg(df, 9).collect()
    from spark_rapids_tpu.tools.reader import log_file_set
    assert len(log_file_set(str(log))) > 1, "log never rotated"
    rows, _profiles, diag, _pv = load_ledger(str(log))
    assert EV.EVENT_SCHEMA_VERSION in diag.header_versions
    assert rows, "no stageProgram rows after rotation round-trip"
    for r in rows:
        assert r.struct_sig and r.norm_sig and r.kind
        json.dumps([c for c in r.consts])   # primitives only


# ---------------------------------------------------------------------------
# plan-invariant verifier
# ---------------------------------------------------------------------------

def test_plan_check_clean_on_tpcds_smoke(tmp_path):
    from spark_rapids_tpu.testing.tpcds import register_tables
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    log = tmp_path / "pc.jsonl"
    PV.reset_observations()
    s = _logged_session(log, **{"spark.rapids.debug.planCheck": "true"})
    register_tables(s, sf=0.02)
    s.sql(QUERIES["q3"]).collect()   # q3 may be empty at this sf
    df = s.create_dataframe(_DATA, num_partitions=2)
    assert _filter_agg(df, 3).collect()
    assert PV.violations_total() == 0
    from spark_rapids_tpu.tools.reader import read_events
    events, _ = read_events(str(log))
    assert not [e for e in events if e.kind == "planInvariantViolation"]


def _apply(session, df):
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    return TpuOverrides(session.conf).apply(df._plan, for_explain=True)


def test_plan_check_catches_removed_materialize_boundary(tmp_path):
    """The hand-broken fixture of the acceptance criteria: splice the
    materialize node out of a lateMaterialization=false plan."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.exec.basic import TpuMaterializeEncodedExec
    path = str(tmp_path / "t.parquet")
    cats = np.array(["a", "b", "c", "d"])
    pq.write_table(pa.table(
        {"s": pa.array(cats[RNG.integers(0, 4, 5000)]),
         "v": RNG.integers(0, 100, 5000)}), path)
    s = tpu_session({
        "spark.rapids.sql.test.enabled": "false",
        "spark.rapids.sql.encoding.lateMaterialization": "false"})
    df = s.read.parquet(path).filter(col("v") > lit(5))
    plan = _apply(s, df)
    assert "TpuMaterializeEncoded" in plan.tree_string()
    PV.reset_observations()
    assert PV.verify_plan(plan, s.conf) == []

    def splice(node):
        kids = []
        for c in node.children:
            if isinstance(c, TpuMaterializeEncodedExec):
                c = c.children[0]       # boundary removed
            splice(c)
            kids.append(c)
        node.children = kids

    splice(plan)
    ring = EV.RingBufferSink()
    EV.add_global_sink(ring)
    try:
        violations = PV.verify_plan(plan, s.conf)
    finally:
        EV.remove_global_sink(ring)
    assert any(v.check == "materialize-boundary" for v in violations)
    evs = [e for e in ring.events()
           if e.kind == "planInvariantViolation"]
    assert evs and evs[0].payload["check"] == "materialize-boundary"
    assert PV.violations_total() >= 1
    PV.reset_observations()


def test_plan_check_catches_stacked_and_orphan_prefetch():
    from spark_rapids_tpu.exec.pipeline import PrefetchExec
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(_DATA, num_partitions=2)
    plan = _apply(s, df.select(Alias(col("k") + lit(1), "k1")))
    PV.reset_observations()
    broken = PrefetchExec(PrefetchExec(plan, "transfer"), "transfer")
    violations = PV.verify_plan(broken, s.conf, emit_events=False)
    assert any("stacked" in v.detail for v in violations
               if v.check == "prefetch-placement")
    # a prefetch node inside a pipeline-disabled plan is also caught
    s2 = tpu_session({"spark.rapids.sql.test.enabled": "false",
                      "spark.rapids.pipeline.enabled": "false"})
    violations2 = PV.verify_plan(PrefetchExec(plan, "transfer"),
                                 s2.conf, emit_events=False)
    assert any("pipeline-disabled" in v.detail for v in violations2)
    # unknown boundary labels are rejected
    violations3 = PV.verify_plan(PrefetchExec(plan, "warp"),
                                 s.conf, emit_events=False)
    assert any("unknown boundary" in v.detail for v in violations3)
    PV.reset_observations()


def test_plan_check_catches_split_exchange():
    """A pass that shallow-copies a shared/reusable exchange apart is
    the exchange-reuse key-consistency breach."""
    import copy
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(_DATA, num_partitions=2)
    plan = _apply(s, df.group_by("k").agg(Alias(F.sum(col("v")), "sv")))
    PV.reset_observations()
    assert PV.verify_plan(plan, s.conf, emit_events=False) == []

    def split_first_exchange(node):
        for i, c in enumerate(node.children):
            if isinstance(c, CpuShuffleExchangeExec):
                twin = copy.copy(c)
                from spark_rapids_tpu.exec.basic import TpuUnionExec
                node.children[i] = TpuUnionExec([c, twin])
                return True
            if split_first_exchange(c):
                return True
        return False

    assert split_first_exchange(plan), "plan has no exchange"
    violations = PV.verify_plan(plan, s.conf, emit_events=False)
    assert any(v.check == "exchange-reuse" for v in violations)
    PV.reset_observations()


def test_plan_check_allows_genuinely_shared_exchange():
    """Reuse WORKING — one exchange instance reached via two parents —
    must not read as two instances sharing a signature."""
    from spark_rapids_tpu.exec.basic import TpuUnionExec
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(_DATA, num_partitions=2)
    plan = _apply(s, df.group_by("k").agg(Alias(F.sum(col("v")), "sv")))
    assert any(isinstance(n, CpuShuffleExchangeExec)
               for n in plan.collect_nodes()), "plan has no exchange"
    shared = TpuUnionExec([plan, plan])     # same instance, two parents
    violations = PV.verify_plan(shared, s.conf, emit_events=False)
    assert not [v for v in violations if v.check == "exchange-reuse"], \
        [v.detail for v in violations]
    PV.reset_observations()


def test_async_compiled_programs_reach_the_ledger(tmp_path):
    """Background (async) compiles run on daemon pool threads; the
    caller's context must travel with the work or every async-built
    program silently vanishes from the audit ledger."""
    log = tmp_path / "async.jsonl"
    s = _logged_session(
        log, **{"spark.rapids.sql.compile.async": "true"})
    SC.clear()
    SC.reset_stats()
    df = s.create_dataframe(_DATA, num_partitions=2)
    out = _filter_agg(df, 4).collect()
    assert out
    rows, _profiles, _diag, _pv = load_ledger(str(log))
    assert rows, "async session recorded no stageProgram rows"
    st = SC.stats()
    assert st["ledger_errors"] == 0


def test_plan_violations_in_prometheus_and_profile(tmp_path):
    text = EV.render_prometheus()
    assert "spark_rapids_tpu_plan_invariant_violations_total" in text
    # the profiler surfaces violations with a !! line
    from spark_rapids_tpu.tools.profile import render_report
    from spark_rapids_tpu.tools.reader import load_profiles
    log = tmp_path / "pv.jsonl"
    with open(log, "w") as f:
        f.write(json.dumps({"event": "eventLogHeader", "query_id": -1,
                            "span_id": -1, "ts": 0.0, "v": 3}) + "\n")
        f.write(json.dumps({"event": "queryStart", "query_id": 1,
                            "span_id": 0, "ts": 1.0,
                            "description": "x", "v": 3}) + "\n")
        f.write(json.dumps({"event": "planInvariantViolation",
                            "query_id": 1, "span_id": 0, "ts": 1.5,
                            "check": "materialize-boundary",
                            "node": "ParquetScan", "detail": "d",
                            "v": 3}) + "\n")
        f.write(json.dumps({"event": "queryEnd", "query_id": 1,
                            "span_id": 0, "ts": 2.0, "duration_s": 1.0,
                            "v": 3}) + "\n")
    profiles, diag = load_profiles(str(log))
    out = render_report(profiles, diag)
    assert "plan-invariant" in out and "materialize-boundary" in out
