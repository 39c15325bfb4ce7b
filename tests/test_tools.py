"""Offline diagnostic toolkit tests: versioned event-log reader
(rotation / gzip / truncation / v1-v2), bottleneck attribution, the
profile/autotune/compare CLI, the live resource sampler, the hardened
JSONL sink, the event-kind catalog, and the Prometheus exposition
format (reference: spark-rapids-tools Qualification/Profiling +
AutoTuner over Spark event logs)."""

import gzip
import json
import os

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux import sampler as SMP
from spark_rapids_tpu.expressions.base import Alias, col
from spark_rapids_tpu.tools import __main__ as CLI
from spark_rapids_tpu.tools.autotune import (autotune, autotune_query,
                                             render_recommendations,
                                             to_conf_dict)
from spark_rapids_tpu.tools.profile import attribute, render_report
from spark_rapids_tpu.tools.reader import load_profiles, read_events

from tests.asserts import tpu_session

RNG = np.random.default_rng(23)
_DATA = {"k": RNG.integers(0, 7, 20000),
         "v": RNG.standard_normal(20000)}


def _run_logged_query(log):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sql.eventLog.path": str(log)})
    df = s.create_dataframe(_DATA, num_partitions=2)
    out = df.group_by("k").agg(Alias(F.sum(col("v")), "sv")).collect()
    return s, out


def _jline(kind, query_id, span_id, ts, v=2, **payload):
    return json.dumps({"event": kind, "query_id": query_id,
                       "span_id": span_id, "ts": ts, "v": v, **payload})


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

def test_reader_roundtrip_tree_and_truncated_tail(tmp_path):
    log = tmp_path / "ev.jsonl"
    s, _ = _run_logged_query(log)
    # torn final line: the process died mid-write
    with open(log, "a") as f:
        f.write('{"event": "spill", "query_id": 1, "by')
    profiles, diag = load_profiles(str(log))
    assert diag.truncated_lines == 1
    assert diag.header_versions == [EV.EVENT_SCHEMA_VERSION]
    assert not diag.unknown_kinds
    assert len(profiles) == 1
    qp = profiles[0]
    assert qp.complete and qp.description == "collect"
    # v2 structure: a real tree (children), per-partition timelines
    spans = qp.exec_spans()
    assert spans, "span tree must reconstruct"
    assert any(sp.children for sp in spans), "tree must have edges"
    assert any(sp.partitions for sp in spans), \
        "partition timelines must survive the round trip"
    for sp in spans:
        for p in sp.partitions:
            assert p["end_s"] >= p["start_s"]
    # queryStart carried the session's non-default conf
    assert "spark.rapids.sql.eventLog.path" in qp.conf


def test_reader_v1_lines_load_flat(tmp_path):
    log = tmp_path / "v1.jsonl"
    lines = [
        _jline("queryStart", 9, 1, 1.0, v=1, description="old"),
        _jline("spanMetrics", 9, 2, 2.0, v=1, node="TpuProjectExec",
               opTime=0.5),
        _jline("spanMetrics", 9, 3, 2.0, v=1, node="TpuFilterExec",
               opTime=0.2),
        _jline("queryEnd", 9, 1, 3.0, v=1, duration_s=2.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    profiles, diag = load_profiles(str(log))
    assert len(profiles) == 1
    qp = profiles[0]
    # no parent_id in v1: spans load as a flat root list, still rankable
    assert len(qp.roots) == 2
    att = attribute(qp)
    assert att.wall_s == 2.0
    assert att.scaled["compute"] > 0


def test_reader_splits_restarted_process_runs(tmp_path):
    """Query ids and monotonic clocks restart per process; two runs
    appending to one log must load as two profiles, not one merged
    corrupt timeline."""
    log = tmp_path / "two_runs.jsonl"

    def run(t0):
        return [
            _jline("queryStart", 1, 1, t0, description="r"),
            _jline("spanMetrics", 1, 2, t0 + 0.5, parent_id=1, depth=1,
                   node="TpuProjectExec", desc="p", opTime=0.4,
                   start_s=t0, end_s=t0 + 1.0),
            _jline("queryEnd", 1, 1, t0 + 1.0, duration_s=1.0),
        ]

    # second run's clock restarted BELOW the first's; run-1 samples sit
    # at timestamps that fall inside run-2's window on run-2's clock
    r1_samples = [_jline("resourceSample", -1, -1, 5.5, pool_used_bytes=9)]
    log.write_text("\n".join(run(100.0) + r1_samples + run(5.0)) + "\n")
    profiles, _ = load_profiles(str(log))
    assert len(profiles) == 2
    assert all(p.complete for p in profiles)
    for p in profiles:
        assert abs(attribute(p).wall_s - 1.0) < 1e-6
        assert len(p.spans) == 1
    # the run-1 sample (ts 5.5) must NOT attach to run-2's query
    # (window [5.0, 6.0] on a DIFFERENT clock)
    assert profiles[1].samples == []


def test_reader_rejects_future_schema(tmp_path):
    log = tmp_path / "future.jsonl"
    log.write_text(_jline("queryStart", 1, 1, 1.0, v=99) + "\n")
    with pytest.raises(ValueError, match="schema v99"):
        read_events(str(log))


def test_reader_missing_file(tmp_path):
    with pytest.raises(FileNotFoundError):
        read_events(str(tmp_path / "nope.jsonl"))


# ---------------------------------------------------------------------------
# sink hardening: rotation, compression, atexit flush
# ---------------------------------------------------------------------------

def test_sink_rotation_and_reader_walks_the_set(tmp_path):
    p = str(tmp_path / "rot.jsonl")
    sink = EV.JsonlEventLogSink(p, max_bytes=400, flush_every=2)
    for i in range(20):
        sink.emit(EV.Event("spill", 1, 2, float(i),
                           {"tier": "device->host", "bytes": i}))
    sink.close()
    rotated = [f for f in os.listdir(tmp_path) if f.startswith("rot.jsonl.")]
    assert rotated, "sink must rotate past maxBytes"
    # every file (fresh and rotated) leads with a schema header
    for name in rotated + ["rot.jsonl"]:
        first = json.loads(open(tmp_path / name).readline())
        assert first["event"] == "eventLogHeader"
        assert first["v"] == EV.EVENT_SCHEMA_VERSION
    events, diag = read_events(p)
    assert len(events) == 20, "reader must walk the whole rotated set"
    assert len(diag.files) == len(rotated) + 1
    assert [e.payload["bytes"] for e in events] == list(range(20))


def test_sink_gzip_compression_roundtrip(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    sink = EV.JsonlEventLogSink(p, compress=True, flush_every=3)
    for i in range(10):
        sink.emit(EV.Event("oom", 4, 1, float(i), {"needed": i}))
    sink.close()
    with open(p, "rb") as f:
        assert f.read(2) == b"\x1f\x8b", "gzip magic expected"
    # multi-member stream decompresses as one concatenation
    text = gzip.decompress(open(p, "rb").read()).decode()
    assert text.count("\n") == 11    # header + 10 events
    events, diag = read_events(p)
    assert [e.payload["needed"] for e in events] == list(range(10))


def test_reader_tolerates_truncated_gzip_tail(tmp_path):
    """A process killed mid-write leaves a partial gzip member; the
    reader must count it as truncation, not crash."""
    p = str(tmp_path / "gz.jsonl")
    sink = EV.JsonlEventLogSink(p, compress=True, flush_every=2)
    for i in range(6):
        sink.emit(EV.Event("oom", 1, 1, float(i), {"needed": i}))
    sink.close()
    data = open(p, "rb").read()
    with open(p, "wb") as f:
        f.write(data[:-20])     # chop mid-member
    events, diag = read_events(p)
    assert diag.truncated_lines >= 1
    assert events, "the intact prefix must still load"
    assert all(e.kind == "oom" for e in events)


def test_sink_rotation_with_shared_path_writers(tmp_path):
    """Two sinks on one path (the sampler + per-query configuration):
    rotation must never lose events or rename a file out from under the
    sibling permanently — stale writers migrate at their next batch."""
    p = str(tmp_path / "shared.jsonl")
    a = EV.JsonlEventLogSink(p, max_bytes=600, flush_every=1)
    b = EV.JsonlEventLogSink(p, max_bytes=600, flush_every=1)
    for i in range(30):
        (a if i % 2 else b).emit(
            EV.Event("spill", 1, 1, float(i),
                     {"bytes": i, "tier": "device->host"}))
    a.close()
    b.close()
    events, _diag = read_events(p)
    assert sorted(e.payload["bytes"] for e in events) == list(range(30))


def test_sink_atexit_flush_preserves_tail(tmp_path):
    p = str(tmp_path / "tail.jsonl")
    sink = EV.JsonlEventLogSink(p)     # default batch of 64: stays pending
    sink.emit(EV.Event("spill", 1, 1, 0.5, {"bytes": 7,
                                            "tier": "device->host"}))
    assert sum(1 for _ in open(p)) == 1, "only the header is on disk yet"
    EV._flush_eventlog_sinks()          # what atexit runs
    lines = open(p).read().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[1])["bytes"] == 7
    sink.close()


def test_eventlog_confs_validated_at_set_conf():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.sql.eventLog.maxBytes", "-5")
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.sql.eventLog.compress", "maybe")
    s.set_conf("spark.rapids.sql.eventLog.maxBytes", "64m")
    assert s.conf.get(C.EVENT_LOG_MAX_BYTES.key) == 64 << 20


# ---------------------------------------------------------------------------
# attribution + profile report
# ---------------------------------------------------------------------------

def test_profile_bucket_total_within_5pct_of_wall(tmp_path):
    log = tmp_path / "ev.jsonl"
    _run_logged_query(log)
    profiles, diag = load_profiles(str(log))
    assert profiles
    for qp in profiles:
        att = attribute(qp)
        assert att.wall_s > 0
        assert abs(att.scaled_total() - att.wall_s) <= 0.05 * att.wall_s, \
            (att.scaled_total(), att.wall_s)
        assert all(v >= 0 for v in att.scaled.values())
    report = render_report(profiles, diag)
    assert "Wall-clock decomposition" in report
    assert "Top operators by exclusive time" in report
    assert "Partition timeline" in report
    assert "bottleneck=" in report


def test_profile_cli(tmp_path, capsys):
    log = tmp_path / "ev.jsonl"
    _run_logged_query(log)
    assert CLI.main(["profile", str(log)]) == 0
    out = capsys.readouterr().out
    assert "== Query " in out and "decomposition" in out
    assert CLI.main(["profile", str(log), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    q = payload["queries"][0]
    assert q["wall_s"] > 0 and q["bottleneck"]
    total = sum(q["buckets_scaled_s"].values())
    assert abs(total - q["wall_s"]) <= 0.05 * q["wall_s"]


def test_profile_report_flags_ring_drops(tmp_path):
    """Satellite contract: ring truncation is surfaced, never silent."""
    log = tmp_path / "drop.jsonl"
    lines = [
        _jline("queryStart", 3, 1, 1.0, description="q"),
        _jline("queryEnd", 3, 1, 2.0, duration_s=1.0, events_dropped=12),
    ]
    log.write_text("\n".join(lines) + "\n")
    profiles, diag = load_profiles(str(log))
    assert diag.dropped_events == 12
    report = render_report(profiles, diag)
    assert "dropped" in report and "lower bound" in report


def test_profile_report_flags_lock_order_violations(tmp_path):
    """A query whose log carries lockOrderViolation events (the runtime
    spark.rapids.debug.lockOrder validator) gets a !! line naming the
    backward edges; a clean query gets none."""
    log = tmp_path / "lock.jsonl"
    lines = [
        _jline("queryStart", 4, 1, 1.0, description="q"),
        _jline("lockOrderViolation", 4, 1, 1.5, held="arbiter",
               acquiring="catalog",
               order="spool<catalog<semaphore<arbiter"),
        _jline("queryEnd", 4, 1, 2.0, duration_s=1.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    profiles, diag = load_profiles(str(log))
    report = render_report(profiles, diag)
    assert "1 lock-order violation(s)" in report
    assert "arbiter->catalog" in report
    clean = tmp_path / "clean.jsonl"
    clean.write_text("\n".join([
        _jline("queryStart", 5, 1, 1.0, description="q"),
        _jline("queryEnd", 5, 1, 2.0, duration_s=1.0)]) + "\n")
    profiles, diag = load_profiles(str(clean))
    assert "lock-order" not in render_report(profiles, diag)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def _stall_heavy_log(tmp_path):
    log = tmp_path / "stall.jsonl"
    lines = [
        _jline("queryStart", 7, 1, 10.0, description="stally",
               conf={"spark.rapids.pipeline.depth": 2}),
        _jline("pipelineSpool", 7, 2, 12.0, boundary="decode", batches=40,
               producer_busy_s=1.0, producer_stall_s=2.4,
               consumer_stall_s=0.05, peak_depth=2),
        _jline("pipelineSpool", 7, 3, 13.0, boundary="transfer", batches=40,
               producer_busy_s=0.8, producer_stall_s=1.1,
               consumer_stall_s=0.02, peak_depth=2),
        _jline("spanMetrics", 7, 4, 14.0, parent_id=1, depth=1,
               node="TpuHashAggregateExec", desc="agg", opTime=1.0,
               start_s=10.0, end_s=15.0),
        _jline("queryEnd", 7, 1, 15.0, duration_s=5.0,
               semaphore_wait_s=0.0, events_dropped=0),
    ]
    log.write_text("\n".join(lines) + "\n")
    return log


def test_autotune_producer_stall_rule(tmp_path):
    """Acceptance: at least one evidence-cited recommendation on a
    stall-heavy synthetic log."""
    log = _stall_heavy_log(tmp_path)
    profiles, _ = load_profiles(str(log))
    recs = autotune(profiles)
    assert recs, "stall-heavy log must produce a recommendation"
    by_key = {r.key: r for r in recs}
    depth = by_key["spark.rapids.pipeline.depth"]
    assert depth.current == 2 and depth.recommended == 4
    assert depth.evidence and any("pipelineSpool" in e
                                  for e in depth.evidence)
    assert "producer" in depth.reason
    conf = to_conf_dict(recs)
    assert conf["spark.rapids.pipeline.depth"] == "4"
    # the emitted dict is genuinely ready-to-apply
    C.TpuConf(dict(conf))
    text = render_recommendations(recs)
    assert "evidence:" in text and "Ready-to-apply conf" in text
    # at the depth cap the rule stays silent instead of emitting a no-op
    profiles[0].conf["spark.rapids.pipeline.depth"] = 16
    capped = autotune_query(profiles[0])
    assert "spark.rapids.pipeline.depth" not in {r.key for r in capped}


def test_autotune_fetch_retry_rule(tmp_path):
    log = tmp_path / "fetch.jsonl"
    lines = [
        _jline("queryStart", 8, 1, 1.0, description="retries"),
        *[_jline("fetchRetry", 8, 1, 1.0 + 0.1 * i, peer="w1",
                 shuffle_id=3, partition=i, attempt=1, wait_ms=300.0)
          for i in range(4)],
        _jline("queryEnd", 8, 1, 3.0, duration_s=2.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    profiles, _ = load_profiles(str(log))
    recs = autotune_query(profiles[0])
    keys = {r.key for r in recs}
    assert "spark.rapids.shuffle.fetch.timeoutMs" in keys
    rec = next(r for r in recs
               if r.key == "spark.rapids.shuffle.fetch.timeoutMs")
    assert rec.current == 30_000 and rec.recommended == 60_000
    assert any("fetchRetry" in e for e in rec.evidence)


def test_autotune_spill_pressure_rule(tmp_path):
    log = tmp_path / "spill.jsonl"
    lines = [
        _jline("queryStart", 9, 1, 1.0, description="spilly",
               conf={"spark.rapids.sql.concurrentGpuTasks": 4}),
        *[_jline("spill", 9, 1, 1.1 + 0.1 * i, tier="device->host",
                 bytes=1 << 20, duration_s=0.2) for i in range(3)],
        _jline("splitRetry", 9, 1, 1.6, task_id=1, pieces=2),
        _jline("queryEnd", 9, 1, 3.0, duration_s=2.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    recs = autotune_query(load_profiles(str(log))[0][0])
    by_key = {r.key: r for r in recs}
    assert by_key["spark.rapids.sql.concurrentGpuTasks"].recommended == 3
    assert by_key["spark.rapids.sql.batchSizeBytes"].recommended \
        == (512 << 20) // 2
    assert any("spill" in e for e in
               by_key["spark.rapids.sql.concurrentGpuTasks"].evidence)


def test_autotune_deadlock_break_rule(tmp_path):
    """Rule 6: repeated deadlock breaks / BUFN splits -> shed device
    concurrency, with the break events as evidence."""
    log = tmp_path / "deadlock.jsonl"
    lines = [
        _jline("queryStart", 11, 1, 1.0, description="contended",
               conf={"spark.rapids.sql.concurrentGpuTasks": 4}),
        _jline("deadlockBreak", 11, 1, 1.2, task_id=7, exc="RetryOOM",
               blocked_tasks=4, forced=False, wake_count=1),
        _jline("deadlockBreak", 11, 1, 1.4, task_id=7,
               exc="SplitAndRetryOOM", blocked_tasks=4, forced=False,
               wake_count=2),
        _jline("queryEnd", 11, 1, 3.0, duration_s=2.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    recs = autotune_query(load_profiles(str(log))[0][0])
    by_key = {r.key: r for r in recs}
    rec = by_key["spark.rapids.sql.concurrentGpuTasks"]
    assert rec.current == 4 and rec.recommended == 3
    assert any("deadlockBreak" in e for e in rec.evidence)
    assert "BUFN split" in rec.reason
    # a single break stays silent: the mechanism doing its job once is
    # not evidence of chronic contention
    single = tmp_path / "one.jsonl"
    single.write_text("\n".join([
        _jline("queryStart", 12, 1, 1.0, description="once"),
        _jline("deadlockBreak", 12, 1, 1.2, task_id=3, exc="RetryOOM",
               blocked_tasks=2, forced=False, wake_count=1),
        _jline("queryEnd", 12, 1, 2.0, duration_s=1.0),
    ]) + "\n")
    assert "spark.rapids.sql.concurrentGpuTasks" not in {
        r.key for r in autotune_query(load_profiles(str(single))[0][0])}


def test_autotune_deadlock_breaks_at_serial_raise_pool_fraction(tmp_path):
    """Rule 6 at concurrentGpuTasks=1: nothing left to shed — recommend
    a bigger pool fraction instead."""
    log = tmp_path / "serial.jsonl"
    lines = [
        _jline("queryStart", 13, 1, 1.0, description="serial",
               conf={"spark.rapids.sql.concurrentGpuTasks": 1}),
        *[_jline("deadlockBreak", 13, 1, 1.0 + 0.1 * i, task_id=5,
                 exc="SplitAndRetryOOM", blocked_tasks=1, forced=False,
                 wake_count=i + 1) for i in range(3)],
        _jline("queryEnd", 13, 1, 3.0, duration_s=2.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    recs = autotune_query(load_profiles(str(log))[0][0])
    by_key = {r.key: r for r in recs}
    rec = by_key["spark.rapids.memory.gpu.allocFraction"]
    assert rec.recommended == pytest.approx(0.9)
    conf = to_conf_dict([rec])
    C.TpuConf(dict(conf))       # genuinely ready-to-apply


def test_autotune_quiet_on_healthy_log(tmp_path):
    log = tmp_path / "ok.jsonl"
    lines = [
        _jline("queryStart", 2, 1, 1.0, description="fine"),
        _jline("spanMetrics", 2, 3, 1.8, parent_id=1, depth=1,
               node="TpuProjectExec", desc="p", opTime=0.9,
               start_s=1.0, end_s=2.0),
        _jline("queryEnd", 2, 1, 2.0, duration_s=1.0,
               semaphore_wait_s=0.01),
    ]
    log.write_text("\n".join(lines) + "\n")
    assert autotune(load_profiles(str(log))[0]) == []


def test_autotune_cli_json(tmp_path, capsys):
    log = _stall_heavy_log(tmp_path)
    assert CLI.main(["autotune", str(log), "--json"]) == 0
    conf = json.loads(capsys.readouterr().out)
    assert conf.get("spark.rapids.pipeline.depth") == "4"


# ---------------------------------------------------------------------------
# bench compare
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# live resource sampler
# ---------------------------------------------------------------------------

def test_sampler_emits_and_results_bit_identical(tmp_path):
    base = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df0 = base.create_dataframe(_DATA, num_partitions=2)
    expect = (df0.group_by("k")
              .agg(Alias(F.sum(col("v")), "sv")).to_pydict())
    log = tmp_path / "s.jsonl"
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sample.enabled": "true",
                     "spark.rapids.sample.intervalMs": "10",
                     "spark.rapids.sql.eventLog.path": str(log)})
    try:
        smp = SMP.active_sampler()
        assert smp is not None and smp.running
        df = s.create_dataframe(_DATA, num_partitions=2)
        got = (df.group_by("k")
               .agg(Alias(F.sum(col("v")), "sv")).to_pydict())
        # bit-for-bit: sampling must never perturb results
        assert got == expect
        payload = smp.sample_once()     # deterministic >= 1 sample
        assert payload["pool_limit_bytes"] > 0
        assert "semaphore_holders" in payload
        assert "prefetch_queued_batches" in payload
        assert "active_tasks" in payload
    finally:
        SMP.stop_sampler()
    assert SMP.active_sampler() is None
    events, _ = read_events(str(log))
    samples = [e for e in events if e.kind == "resourceSample"]
    assert samples, "samples must land in the event log"
    assert all(e.query_id == EV.NO_QUERY for e in samples)
    # sampler sink unregistered: later emits go nowhere
    n = len(samples)
    EV.emit("resourceSample", probe=1)
    events2, _ = read_events(str(log))
    assert len([e for e in events2 if e.kind == "resourceSample"]) == n


def test_sampler_confs_validated_at_set_conf():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    try:
        with pytest.raises(ValueError):
            s.set_conf("spark.rapids.sample.intervalMs", "0")
        with pytest.raises(ValueError):
            s.set_conf("spark.rapids.sample.intervalMs", "nope")
        with pytest.raises(ValueError):
            s.set_conf("spark.rapids.sample.enabled", "maybe")
        with pytest.raises(ValueError):
            C.TpuConf({"spark.rapids.sample.intervalMs": "-1"})
        # toggling through set_conf starts and stops the singleton
        s.set_conf("spark.rapids.sample.enabled", "true")
        assert SMP.active_sampler() is not None
        s.set_conf("spark.rapids.sample.enabled", "false")
        assert SMP.active_sampler() is None
    finally:
        SMP.stop_sampler()


def test_sample_payload_reflects_pool_state(tmp_path):
    """collect_sample reads the real catalog: registering a device batch
    moves the gauges."""
    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    from spark_rapids_tpu.memory.device_manager import get_runtime
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    rt = get_runtime()
    assert rt is not None
    before = SMP.collect_sample()
    hb = batch_from_pydict({"a": np.arange(4096, dtype=np.int64)})
    h = rt.catalog.add_device_batch(hb.to_device())
    try:
        after = SMP.collect_sample()
        assert after["pool_used_bytes"] > before["pool_used_bytes"]
        assert after["spillable_bytes"] > 0
        assert after["pool_peak_bytes"] >= after["pool_used_bytes"]
    finally:
        rt.catalog.remove(h)


# ---------------------------------------------------------------------------
# event-kind catalog (migrated into the lint rule `event-catalog`; these
# thin tier-1 wrappers keep the invariant in this suite)
# ---------------------------------------------------------------------------

def _run_event_catalog_rule():
    from spark_rapids_tpu.tools.lint import run_lint
    from spark_rapids_tpu.tools.lint.rules import EventCatalogRule
    return run_lint(rules=[EventCatalogRule()], baseline_path="")


def test_every_emit_call_site_uses_cataloged_kind():
    """Every emit()/record_event kind literal is cataloged — now a lint
    rule (tools/lint rules.py `event-catalog`); this wrapper runs the
    rule and asserts zero findings."""
    report = _run_event_catalog_rule()
    offenders = [f.location + ": " + f.message
                 for f in report.active
                 if "not in EVENT_KINDS" in f.message]
    assert not offenders, f"emit sites using uncataloged kinds: {offenders}"


def test_catalog_covers_no_dead_kinds():
    """Every cataloged kind is referenced outside the catalog — the dead
    direction of the same lint rule."""
    report = _run_event_catalog_rule()
    dead = [f.location + ": " + f.message
            for f in report.active if "never referenced" in f.message]
    assert not dead, f"cataloged kinds never referenced: {dead}"


# ---------------------------------------------------------------------------
# Prometheus exposition + ring drop counter
# ---------------------------------------------------------------------------

def _parse_prometheus(text):
    types, samples = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ")
            types[name] = mtype
        elif line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)
    return types, samples


def test_ring_drops_surface_in_prometheus():
    before = EV.ring_dropped_total()
    ring = EV.RingBufferSink(capacity=2)
    for i in range(7):
        ring.emit(EV.Event("spill", 1, 1, float(i), {}))
    assert ring.dropped == 5
    assert EV.ring_dropped_total() - before == 5
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    types, samples = _parse_prometheus(EV.render_prometheus())
    name = "spark_rapids_tpu_events_ring_dropped_total"
    assert types[name] == "counter"
    assert samples[name] >= 5


def test_a_ring_collected_under_the_drop_lock_does_not_deadlock():
    """A garbage collection can run a ring's finalizer on a thread that
    holds the drop lock (a new ring registering its cell): the finalizer
    must not wait for that lock, and the ring's drops stay counted."""
    import gc
    import threading
    ring = EV.RingBufferSink(capacity=1)
    for i in range(4):
        ring.emit(EV.Event("spill", 1, 1, float(i), {}))
    before = EV.ring_dropped_total()
    ring.cycle = [ring]
    del ring

    def collect_holding_the_lock():
        with EV._DROP_LOCK:
            gc.collect()
    t = threading.Thread(target=collect_holding_the_lock, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "the ring's finalizer waited for the drop lock"
    assert EV.ring_dropped_total() == before


def test_prometheus_format_types_escaping_monotonicity():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    s.create_dataframe({"a": np.arange(200, dtype=np.int64)}).count()
    text1 = EV.render_prometheus()
    types1, samples1 = _parse_prometheus(text1)
    # every sample line's metric family has a TYPE line (histogram
    # series sample as <family>_bucket/_sum/_count under one TYPE line)
    for name in samples1:
        family = name.split("{")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            base = family[:-len(suffix)] if family.endswith(suffix) else ""
            if types1.get(base) == "histogram":
                family = base
                break
        assert family in types1, f"sample {name} missing # TYPE"
    # new gauges are present
    assert "spark_rapids_tpu_device_pool_peak_bytes" in samples1
    assert "spark_rapids_tpu_device_spillable_bytes" in samples1
    # label escaping: quotes/backslashes in a label must not corrupt
    assert EV.escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    # counter monotonicity across more work
    s.create_dataframe({"a": np.arange(200, dtype=np.int64)}).count()
    _, samples2 = _parse_prometheus(EV.render_prometheus())
    for name, mtype in types1.items():
        if mtype != "counter" or name not in samples2:
            continue
        assert samples2.get(name, 0.0) >= samples1.get(name, 0.0), \
            f"counter {name} went backwards"


# ---------------------------------------------------------------------------
# SPMD distribution: ici bucket, Distribution line, AutoTuner rule 10
# ---------------------------------------------------------------------------

def _ici_log(tmp_path, mesh_align_conf=None, aligned=True):
    log = tmp_path / "ici.jsonl"
    conf = {}
    if mesh_align_conf is not None:
        conf["spark.rapids.sql.adaptive.meshAlign"] = mesh_align_conf
    lines = [
        _jline("queryStart", 21, 1, 1.0, description="mesh q",
               conf=conf),
        _jline("exchangeElided", 21, 1, 1.1, count=2,
               exchanges=["HashPartitioning(k, 8) <= hash[1k,8]",
                          "HashPartitioning(k, 8) <= hash[1k,8]"]),
        _jline("iciExchange", 21, 1, 1.3, devices=8, rows=4000,
               shard_rows=[500] * 8, shard_bytes=1 << 16,
               duration_s=0.4),
        _jline("aqeCoalesce", 21, 1, 1.5, before=16,
               after=8 if aligned else 5, align=8 if aligned else 1,
               mesh=8, ici_active=True, aligned=aligned),
        _jline("spanMetrics", 21, 4, 1.9, parent_id=1, depth=1,
               node="TpuShuffleExchangeExec", desc="x", opTime=0.6,
               start_s=1.0, end_s=2.0),
        _jline("queryEnd", 21, 1, 2.0, duration_s=1.0),
    ]
    log.write_text("\n".join(lines) + "\n")
    return log


def test_profile_ici_bucket_and_distribution_line(tmp_path):
    log = _ici_log(tmp_path)
    profiles, diag = load_profiles(str(log))
    att = attribute(profiles[0])
    assert att.raw["ici"] == pytest.approx(0.4)
    report = render_report(profiles, diag)
    assert "ici" in report
    assert "Distribution: exchangeElided=2 iciExchanges=1" in report
    assert "4000 rows moved in-mesh" in report


def test_autotune_rule10_mesh_misaligned_coalesce(tmp_path):
    """Rule 10: misaligned AQE counts while the ICI path is active and
    meshAlign is OFF -> recommend enabling it, with the aqeCoalesce
    events as evidence."""
    log = _ici_log(tmp_path, mesh_align_conf=False, aligned=False)
    recs = autotune_query(load_profiles(str(log))[0][0])
    by_key = {r.key: r for r in recs}
    rec = by_key["spark.rapids.sql.adaptive.meshAlign"]
    assert rec.current is False and rec.recommended is True
    assert any("aqeCoalesce" in e for e in rec.evidence)
    assert "8-device mesh" in rec.reason
    conf = to_conf_dict([rec])
    C.TpuConf(dict(conf))    # genuinely ready-to-apply


def test_autotune_rule10_quiet_when_aligned_or_enabled(tmp_path):
    # aligned decisions: healthy, no recommendation
    log = _ici_log(tmp_path, mesh_align_conf=False, aligned=True)
    keys = {r.key for r in autotune_query(load_profiles(str(log))[0][0])}
    assert "spark.rapids.sql.adaptive.meshAlign" not in keys
    # misaligned but meshAlign already ON (alignment unachievable):
    # there is no conf to apply — stay silent
    log2 = tmp_path / "on.jsonl"
    log2.write_text(_ici_log(tmp_path, mesh_align_conf=True,
                             aligned=False).read_text())
    keys2 = {r.key
             for r in autotune_query(load_profiles(str(log2))[0][0])}
    assert "spark.rapids.sql.adaptive.meshAlign" not in keys2
