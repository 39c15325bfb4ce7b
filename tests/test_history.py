"""History warehouse tests: mixed-schema directory ingest with pinned
per-version row counts, the trajectory sentinel (injected slowdown vs
healthy repeat), bench-payload ingest, machine-profile calibration, the
``== Cost ==`` explain section + queryEnd cross-check, and the
noise-aware regression core behind ``history regress``
(docs/history.md)."""

import json
import os

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.expressions.base import Alias, col
from spark_rapids_tpu.tools import __main__ as CLI
from spark_rapids_tpu.tools.history import (HistoryWarehouse, calibrate,
                                            regress)
from spark_rapids_tpu.tools.history.calibrate import (
    MACHINE_PROFILE_SCHEMA, family_for_node)
from spark_rapids_tpu.tools.regression import detect

from tests.asserts import tpu_session

_DATA = {"k": np.arange(4000, dtype=np.int64) % 7,
         "v": np.linspace(0.0, 1.0, 4000)}


def _jline(kind, query_id, span_id, ts, v=4, **payload):
    return json.dumps({"event": kind, "query_id": query_id,
                       "span_id": span_id, "ts": ts, "v": v, **payload})


def _run_logged_query(log, extra=None):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sql.eventLog.path": str(log),
                     **(extra or {})})
    df = s.create_dataframe(_DATA, num_partitions=2)
    out = df.group_by("k").agg(Alias(F.sum(col("v")), "sv")).collect()
    return s, df, out


def _synth_query(lines, qid, wall_s, v=4, base_ts=0.0):
    """One complete synthetic query: start, two spans, end."""
    lines.append(_jline("queryStart", qid, 1, base_ts, v=v,
                        description="synth"))
    lines.append(_jline("spanMetrics", qid, 2, base_ts + wall_s, v=v,
                        node="TpuFusedAggExec", opTime=wall_s * 0.6,
                        rows=100, batches=2))
    lines.append(_jline("spanMetrics", qid, 3, base_ts + wall_s, v=v,
                        node="TpuCoalesceBatchesExec",
                        opTime=wall_s * 0.2, rows=100, batches=2))
    lines.append(_jline("queryEnd", qid, 1, base_ts + wall_s, v=v,
                        duration_s=wall_s, status="ok", tasks=2))


# ---------------------------------------------------------------------------
# mixed-schema directory ingest
# ---------------------------------------------------------------------------

def test_mixed_schema_directory_ingest_pinned_counts(tmp_path):
    d = tmp_path / "logs"
    d.mkdir()
    # v1: flat spans, no header, no ledger events
    v1 = [
        _jline("queryStart", 9, 1, 1.0, v=1, description="old"),
        _jline("spanMetrics", 9, 2, 2.0, v=1, node="TpuProjectExec",
               opTime=0.5),
        _jline("spanMetrics", 9, 3, 2.0, v=1, node="TpuFilterExec",
               opTime=0.2),
        _jline("queryEnd", 9, 1, 3.0, v=1, duration_s=2.0),
    ]
    (d / "v1.jsonl").write_text("\n".join(v1) + "\n")
    # v3: spans + the compiled-program ledger
    v3 = [_jline("eventLogHeader", -1, 0, 0.0, v=3)]
    _synth_query(v3, 5, 1.0, v=3)
    v3.insert(3, _jline("stageProgram", 5, 2, 0.5, v=3,
                        stage_kind="fused.agg", key="k1", flops=1e6,
                        bytes_accessed=1e5, eqns=4, n_args=2))
    v3.insert(4, _jline("stageProgram", 5, 3, 0.6, v=3,
                        stage_kind="batch.coalesce", key="k2",
                        flops=0.0, bytes_accessed=2e5, eqns=1, n_args=1))
    (d / "v3.jsonl").write_text("\n".join(v3) + "\n")
    # v4: rotated pair (the .1 sibling rides with its base as ONE run)
    # + transition/spill ledger events
    old = [_jline("eventLogHeader", -1, 0, 0.0, v=4)]
    _synth_query(old, 1, 1.0, v=4)
    (d / "v4.jsonl.1").write_text("\n".join(old) + "\n")
    new = [_jline("eventLogHeader", -1, 0, 0.0, v=4)]
    _synth_query(new, 2, 1.1, v=4, base_ts=10.0)
    new.insert(2, _jline("hostTransition", 2, 2, 10.1, v=4,
                         direction="h2d", bytes=4096, duration_s=0.01))
    new.insert(3, _jline("deviceSync", 2, 2, 10.2, v=4,
                         duration_s=0.002))
    new.insert(4, _jline("spill", 2, 2, 10.3, v=4, tier="host->disk",
                         bytes=100, logical_bytes=400, codec="lz4",
                         duration_s=0.001))
    (d / "v4.jsonl").write_text("\n".join(new) + "\n")

    with HistoryWarehouse(str(tmp_path / "h.db")) as wh:
        runs = wh.ingest(str(d), label="mixed")
        # 3 runs: v1, v3, and the v4 rotated SET (not 4)
        assert len(runs) == 3
        by_src = {os.path.basename(r["source"]): r for r in runs}
        assert set(by_src) == {"v1.jsonl", "v3.jsonl", "v4.jsonl"}
        # pinned per-version counts
        assert by_src["v1.jsonl"]["queries"] == 1
        assert by_src["v1.jsonl"]["spans"] == 2
        assert by_src["v1.jsonl"]["programs"] == 0
        assert by_src["v3.jsonl"]["queries"] == 1
        assert by_src["v3.jsonl"]["spans"] == 2
        assert by_src["v3.jsonl"]["programs"] == 2
        assert by_src["v3.jsonl"]["schema_versions"] == [3]
        assert by_src["v4.jsonl"]["queries"] == 2
        assert by_src["v4.jsonl"]["spans"] == 4
        assert by_src["v4.jsonl"]["transitions"] == 2   # h2d + sync
        assert by_src["v4.jsonl"]["spills"] == 1
        rep = wh.report()
        assert rep["tables"]["runs"] == 3
        assert rep["tables"]["queries"] == 4
        assert rep["tables"]["stage_programs"] == 2


# ---------------------------------------------------------------------------
# trajectory sentinel
# ---------------------------------------------------------------------------

def _ingest_synth_run(wh, tmp_path, name, wall_s):
    lines = [_jline("eventLogHeader", -1, 0, 0.0, v=4)]
    _synth_query(lines, 1, wall_s)
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return wh.ingest_log(str(p))


def test_regress_quiet_on_healthy_and_nonzero_on_slowdown(tmp_path):
    with HistoryWarehouse(str(tmp_path / "h.db")) as wh:
        for i, w in enumerate((1.00, 1.02, 0.98)):
            _ingest_synth_run(wh, tmp_path, f"b{i}.jsonl", w)
        # healthy latest: inside the noise band -> quiet, exit 0
        _ingest_synth_run(wh, tmp_path, "healthy.jsonl", 1.01)
        out = regress(wh, min_runs=3)
        assert out["exit_code"] == 0 and out["regressions"] == 0
        assert out["checked"] == 1
        # injected 2x slowdown -> nonzero exit, named verdict
        _ingest_synth_run(wh, tmp_path, "slow.jsonl", 2.0)
        out = regress(wh, min_runs=3)
        assert out["exit_code"] == 1 and out["regressions"] == 1
        bad = [v for d in out["domains"] for v in d["verdicts"]
               if v.get("regression")]
        assert bad and "wall_s" in bad[0]["key"]
        # a thin baseline is SKIPPED, never judged
        thin = regress(wh, min_runs=50)
        assert thin["exit_code"] == 0 and thin["skipped"] >= 1


def test_bench_payload_ingest_failed_runs_never_baseline(tmp_path):
    ok = {"value": 1_000_000, "tpu_s": 1.0, "rows": 1_000_000}
    with HistoryWarehouse(str(tmp_path / "h.db")) as wh:
        for _ in range(3):
            r = wh.ingest_payload(dict(ok))
            assert r["status"] == "ok" and r["metrics"] >= 2
        # placeholder-zero payload records as FAILED with no metrics
        r = wh.ingest_payload({"value": 0, "error": "device lost",
                               "budget_exceeded": True})
        assert r["status"] == "failed" and r["metrics"] == 0
        # latest OK run (not the failed one) is judged: 10x slower
        wh.ingest_payload({"value": 100_000, "tpu_s": 10.0})
        out = regress(wh, min_runs=3)
        assert out["exit_code"] == 1
        keys = [v["key"] for d in out["domains"]
                for v in d["verdicts"] if v.get("regression")]
        assert any("rows/s" in k for k in keys)
        # a payload FILE: the last parseable JSON line is the payload, and
        # a budget alarm beside a real primary value is a measurement
        p = tmp_path / "capture.json"
        p.write_text("stderr noise\n" + json.dumps({"value": 1}) + "\n"
                     + json.dumps({"value": 2, "budget_exceeded": True})
                     + "\n")
        r = wh.ingest(str(p))[0]
        assert r["kind"] == "bench" and r["status"] == "ok"
        assert wh.query("SELECT value FROM bench_metrics WHERE run_id = ?"
                        " AND path = 'value'", (r["run_id"],)) == [(2.0,)]


def test_regress_band_widens_with_the_baseline_noise():
    # MAD band: a noisy baseline widens its own band instead of flagging
    noisy = [1.0, 1.4, 0.7, 1.2, 0.8]
    v = detect(noisy, 1.45, higher_better=False)
    assert not v["regression"]
    tight = [1.0, 1.01, 0.99, 1.0, 1.0]
    v = detect(tight, 1.45, higher_better=False)
    assert v["regression"]


# ---------------------------------------------------------------------------
# calibration + the cost model loop
# ---------------------------------------------------------------------------

def test_calibrate_explain_cost_and_crosscheck(tmp_path):
    log = tmp_path / "ev.jsonl"
    db = str(tmp_path / "h.db")
    prof_path = str(tmp_path / "machine.json")
    _, _, baseline_out = _run_logged_query(log)
    _run_logged_query(log)
    with HistoryWarehouse(db) as wh:
        rs = wh.ingest(str(log), label="cal")
        assert rs and rs[0]["queries"] >= 2
        profile = calibrate(wh)
    # the artifact's honesty clause: the reported bound must cover >=80%
    # of its own observations (acceptance: p90 by construction)
    assert profile["schema"] == MACHINE_PROFILE_SCHEMA
    assert profile["stage_kinds"]
    assert profile["within_bound_frac"] >= 0.8
    assert profile["observations"] > 0
    for fit in profile["stage_kinds"].values():
        assert fit["fixed_s_per_batch"] >= 0.0
        assert fit["per_row_s"] >= 0.0
    with open(prof_path, "w") as f:
        json.dump(profile, f)

    # run WITH the profile: explain renders == Cost ==, the result is
    # bit-identical (report-only), and queryEnd carries the cross-check
    log2 = tmp_path / "ev2.jsonl"
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sql.eventLog.path": str(log2),
                     "spark.rapids.history.machineProfilePath": prof_path})
    df = s.create_dataframe(_DATA, num_partitions=2)
    q = df.group_by("k").agg(Alias(F.sum(col("v")), "sv"))
    exp = q.explain()
    assert "== Cost ==" in exp
    assert "machine profile v1" in exp
    assert "predicted total" in exp
    out = q.collect()
    assert out == baseline_out          # trimodal bit-identity
    from spark_rapids_tpu.aux.tracing import last_query_summary
    cost = last_query_summary().get("cost")
    assert cost is not None
    assert cost["predicted_s"] > 0 and cost["measured_s"] > 0
    assert cost["covered"] >= 1
    assert cost["residual_bound"] == profile["residual_bound"]
    # the residual landed in the event log for tools audit
    from spark_rapids_tpu.tools.reader import load_profiles
    profiles, _ = load_profiles(str(log2))
    ev = [e for qp in profiles for e in qp.events_of("costModel")]
    assert ev and ev[0].payload["predicted_s"] == cost["predicted_s"]
    from spark_rapids_tpu.tools.audit.passes import run_audit
    rep = run_audit(str(log2))
    assert rep.cost_checks and \
        rep.cost_checks[0]["predicted_s"] == cost["predicted_s"]

    # cost model off (conf) -> no section, identical results
    s2 = tpu_session({"spark.rapids.sql.test.enabled": "false",
                      "spark.rapids.history.machineProfilePath": prof_path,
                      "spark.rapids.history.costModel.enabled": "false"})
    df2 = s2.create_dataframe(_DATA, num_partitions=2)
    q2 = df2.group_by("k").agg(Alias(F.sum(col("v")), "sv"))
    assert "== Cost ==" not in q2.explain()
    assert q2.collect() == baseline_out


def test_calibrate_needs_event_log_runs(tmp_path):
    with HistoryWarehouse(str(tmp_path / "h.db")) as wh:
        wh.ingest_payload({"value": 10, "tpu_s": 1.0})
        with pytest.raises(ValueError):
            calibrate(wh)


def test_family_for_node_is_the_audit_vocabulary():
    assert family_for_node("TpuFusedAggExec") == "fused.agg"
    assert family_for_node("TpuHashAggregateExec") == "agg."
    assert family_for_node("HostToDeviceExec") == "transfer.pack"
    assert family_for_node("DeviceToHostExec") == "transfer.unpack"
    assert family_for_node("SomethingUnknownExec") is None


def test_unreadable_profile_never_fails_explain(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"schema": "not-a-profile"}')
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.history.machineProfilePath": str(bad)})
    df = s.create_dataframe(_DATA, num_partitions=2)
    exp = df.group_by("k").agg(Alias(F.sum(col("v")), "sv")).explain()
    assert "machine profile unreadable" in exp


# ---------------------------------------------------------------------------
# CLI round trip
# ---------------------------------------------------------------------------

def test_history_cli_round_trip(tmp_path, capsys):
    log = tmp_path / "ev.jsonl"
    db = str(tmp_path / "h.db")
    prof = str(tmp_path / "machine.json")
    _run_logged_query(log)
    assert CLI.main(["history", "ingest", str(log), "--db", db,
                     "--label", "r1"]) == 0
    assert CLI.main(["history", "ingest", str(log), "--db", db,
                     "--label", "r2"]) == 0
    assert CLI.main(["history", "report", "--db", db]) == 0
    assert CLI.main(["history", "regress", "--db", db,
                     "--min-runs", "1"]) == 0
    assert CLI.main(["history", "calibrate", "--db", db,
                     "-o", prof]) == 0
    doc = json.load(open(prof))
    assert doc["schema"] == MACHINE_PROFILE_SCHEMA
    cap = capsys.readouterr().out
    assert "wrote machine profile" in cap
    # no --db and no conf default -> usage error, not a traceback
    assert C.HISTORY_PATH.default == ""
    assert CLI.main(["history", "report"]) == 2


def test_history_conf_keys_registered_and_evented():
    # the new keys are in the registry (conf-registry lint contract)
    reg = C.registry()
    for entry in (C.HISTORY_PATH, C.HISTORY_MACHINE_PROFILE_PATH,
                  C.HISTORY_COST_MODEL_ENABLED,
                  C.HISTORY_REGRESS_MIN_RUNS,
                  C.HISTORY_REGRESS_MAD_BANDS):
        assert entry.key in reg
    # and the cross-check event kind is cataloged
    assert "costModel" in EV.EVENT_KINDS


# ---------------------------------------------------------------------------
# ingest idempotency (content digest)
# ---------------------------------------------------------------------------

def test_ingest_idempotent_by_content_digest(tmp_path):
    log = tmp_path / "ev.jsonl"
    db = str(tmp_path / "h.db")
    _run_logged_query(log)
    with HistoryWarehouse(db) as wh:
        r1 = wh.ingest(str(log), label="first")[0]
        assert not r1["updated"]
        n_queries = wh.query("SELECT COUNT(*) FROM queries")[0][0]
        # same path + same content: the run row UPDATES in place
        r2 = wh.ingest(str(log), label="second")[0]
        assert r2["updated"] and r2["run_id"] == r1["run_id"]
        runs = wh.runs()
        assert len(runs) == 1 and runs[0]["label"] == "second"
        # child rows purged and re-inserted, never doubled
        assert wh.query("SELECT COUNT(*) FROM queries")[0][0] == n_queries
        # changed content (one more query appended) -> a NEW run
        _run_logged_query(log)
        r3 = wh.ingest(str(log), label="third")[0]
        assert not r3["updated"] and r3["run_id"] != r1["run_id"]
        assert len(wh.runs()) == 2
        # force=True always inserts, identical content or not
        r4 = wh.ingest(str(log), label="forced", force=True)[0]
        assert not r4["updated"]
        assert r4["run_id"] not in (r1["run_id"], r3["run_id"])
        assert len(wh.runs()) == 3
        # dict payloads have no path identity: they always insert
        p1 = wh.ingest_payload({"value": 10, "tpu_s": 1.0})
        p2 = wh.ingest_payload({"value": 10, "tpu_s": 1.0})
        assert p1["run_id"] != p2["run_id"]


def test_history_cli_ingest_force_flag(tmp_path, capsys):
    log = tmp_path / "ev.jsonl"
    db = str(tmp_path / "h.db")
    _run_logged_query(log)
    assert CLI.main(["history", "ingest", str(log), "--db", db,
                     "--label", "a"]) == 0
    assert CLI.main(["history", "ingest", str(log), "--db", db,
                     "--label", "b"]) == 0
    assert "updated (same content)" in capsys.readouterr().out
    with HistoryWarehouse(db) as wh:
        assert len(wh.runs()) == 1
    assert CLI.main(["history", "ingest", str(log), "--db", db,
                     "--label", "c", "--force"]) == 0
    with HistoryWarehouse(db) as wh:
        assert len(wh.runs()) == 2
