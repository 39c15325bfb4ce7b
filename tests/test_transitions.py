"""Host-transition & device-sync ledger tests: the aux/transitions
gateway (counters, snapshot/delta, conf gating), schema-v4 events and
reader back-compat (v1-v3 still load), the per-query ledger riding
queryEnd into summaries / explain(analyze) / tools profile, the
Chrome-trace ``tools trace`` export (format validation + CLI +
unattributed check), serving latency histograms in the Prometheus
exposition, and the trimodal bit-identity guarantee (instrumentation
on/off never changes results)."""

import json
import math

import numpy as np
import pytest

from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.tools import __main__ as CLI
from spark_rapids_tpu.tools.reader import (SUPPORTED_VERSIONS,
                                           load_profiles, read_events)
from spark_rapids_tpu.tools.trace import (build_trace, render_trace,
                                          trace_from_log,
                                          unattributed_transitions)

from tests.asserts import tpu_session

RNG = np.random.default_rng(31)
_N = 20_000
_DATA = {"k": RNG.integers(0, 11, _N), "v": RNG.standard_normal(_N)}


def _run_logged_query(log, extra=None):
    conf = {"spark.rapids.sql.test.enabled": "false",
            "spark.rapids.sql.eventLog.path": str(log)}
    conf.update(extra or {})
    s = tpu_session(conf)
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.expressions.base import Alias, col
    df = s.create_dataframe(_DATA, num_partitions=2)
    out = df.group_by("k").agg(Alias(F.sum(col("v")), "sv")).collect()
    return s, out


def _jline(kind, query_id, span_id, ts, v=EV.EVENT_SCHEMA_VERSION,
           **payload):
    return json.dumps({"event": kind, "query_id": query_id,
                       "span_id": span_id, "ts": ts, "v": v, **payload})


# ---------------------------------------------------------------------------
# the gateway: the process's ledger and the active query's, conf gating
# ---------------------------------------------------------------------------

def _since(start):
    """What the process's ledger gained since ``start = TR.totals()``."""
    return {k: v - start[k] for k, v in TR.totals().items()}


def test_gateway_counters_and_the_querys_ledger():
    from spark_rapids_tpu.aux.tracing import QueryExecution
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    start = TR.totals()
    with QueryExecution(description="hand-made") as qe:
        TR.record_h2d(1000, 0.25, kinds="dict,flat", planes=3)
        TR.record_d2h(400, 0.125, site="download")
    TR.record_d2h(77, 0.5)          # outside any query: the process's only
    d = qe.summary_dict["transitions"]
    assert d["h2d_count"] == 1 and d["h2d_bytes"] == 1000
    assert d["d2h_count"] == 1 and d["d2h_bytes"] == 400
    assert abs(d["h2d_s"] - 0.25) < 1e-9
    assert abs(d["d2h_s"] - 0.125) < 1e-9
    # ledger keys are the fixed 8-key schema, all JSON-scalar
    assert set(d) == {"h2d_count", "h2d_bytes", "h2d_s", "d2h_count",
                      "d2h_bytes", "d2h_s", "sync_count", "sync_s"}
    total = _since(start)
    assert total["h2d_count"] == 1 and total["d2h_count"] == 2
    assert total["d2h_bytes"] == 477
    assert abs(total["d2h_seconds"] - 0.625) < 1e-6


def test_gateway_fetch_and_sync_count_once():
    """fetch()/sync_int() are deviceSyncs (count forces, scalar syncs);
    only record_d2h (the packed batch download) lands in d2h_* — one
    boundary crossing is never counted in BOTH ledger columns."""
    import jax.numpy as jnp
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    start = TR.totals()
    host = TR.fetch(jnp.arange(128), site="test-fetch")
    assert host.shape == (128,)
    n = TR.sync_int(jnp.asarray(7), site="test-count")
    assert n == 7
    d = _since(start)
    assert d["sync_count"] == 2 and d["sync_seconds"] >= 0.0
    assert d["d2h_count"] == 0, \
        "sync-site fetches must land in sync_*, not d2h_*"


def test_gateway_conf_disable_stops_counting():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    try:
        s.set_conf("spark.rapids.sql.transitions.enabled", "false")
        assert not TR.enabled()
        start = TR.totals()
        TR.record_h2d(999, 0.5)
        TR.record_d2h(999, 0.5)
        d = _since(start)
        assert d["h2d_count"] == 0 and d["d2h_count"] == 0
    finally:
        s.set_conf("spark.rapids.sql.transitions.enabled", "true")
        assert TR.enabled()


# ---------------------------------------------------------------------------
# schema v4: events in the log, ledger on queryEnd, reader back-compat
# ---------------------------------------------------------------------------

def test_query_emits_v4_transition_events_and_ledger(tmp_path):
    log = tmp_path / "tr.jsonl"
    _run_logged_query(log)
    events, diag = read_events(str(log))
    assert diag.header_versions == [4]
    kinds = {e.kind for e in events}
    assert "hostTransition" in kinds
    ht = [e for e in events if e.kind == "hostTransition"]
    for e in ht:
        assert e.payload["direction"] in ("h2d", "d2h")
        assert e.payload["bytes"] > 0
        assert e.payload["duration_s"] >= 0.0
        assert e.query_id != EV.NO_QUERY, \
            "transitions during a query must be attributed to it"
    assert {e.payload["direction"] for e in ht} == {"h2d", "d2h"}
    # the queryEnd summary carries the per-query ledger
    qend = [e for e in events if e.kind == "queryEnd"][-1]
    ledger = qend.payload["transitions"]
    assert ledger["h2d_count"] >= 1 and ledger["d2h_count"] >= 1
    assert ledger["h2d_bytes"] > 0 and ledger["d2h_bytes"] > 0


def test_reader_supported_versions_v1_through_v4(tmp_path):
    assert SUPPORTED_VERSIONS == (1, 2, 3, 4)
    # one log per historical version must still load
    for v in (1, 2, 3):
        log = tmp_path / f"v{v}.jsonl"
        lines = [
            _jline("queryStart", 3, 1, 1.0, v=v, description="old"),
            _jline("spanMetrics", 3, 2, 2.0, v=v, node="TpuProjectExec",
                   opTime=0.5),
            _jline("queryEnd", 3, 1, 3.0, v=v, duration_s=2.0),
        ]
        log.write_text("\n".join(lines) + "\n")
        profiles, diag = load_profiles(str(log))
        assert len(profiles) == 1, f"v{v} log must still load"
        assert not diag.unknown_kinds


def test_explain_analyze_renders_transition_footer(tmp_path):
    log = tmp_path / "ex.jsonl"
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sql.eventLog.path": str(log)})
    df = s.create_dataframe(_DATA, num_partitions=2)
    text = df.explain(analyze=True)
    assert "== Transitions ==" in text
    assert "d2h" in text


# ---------------------------------------------------------------------------
# tools profile: transitions + sync buckets, ledger in JSON output
# ---------------------------------------------------------------------------

def test_profile_buckets_and_json_ledger(tmp_path):
    from spark_rapids_tpu.tools.profile import (BUCKETS, attribute,
                                                profiles_to_json,
                                                render_report)
    assert "transitions" in BUCKETS and "sync" in BUCKETS
    log = tmp_path / "prof.jsonl"
    _run_logged_query(log)
    profiles, diag = load_profiles(str(log))
    att = attribute(profiles[-1])
    assert att.scaled["transitions"] > 0.0, \
        "a collect() query crosses the boundary at least once"
    report = render_report(profiles, diag)
    assert "Transitions:" in report
    payload = profiles_to_json(profiles, diag)
    led = payload["queries"][-1]["transitions"]
    assert led["d2h_count"] >= 1 and led["d2h_bytes"] > 0


def test_profile_ledger_survives_event_ring_drop(tmp_path):
    """Attribution must fall back to the queryEnd ledger when the
    individual hostTransition events were dropped/filtered."""
    from spark_rapids_tpu.tools.profile import attribute
    log = tmp_path / "drop.jsonl"
    _run_logged_query(log)
    kept = [ln for ln in open(log).read().splitlines()
            if '"hostTransition"' not in ln and '"deviceSync"' not in ln]
    slim = tmp_path / "slim.jsonl"
    slim.write_text("\n".join(kept) + "\n")
    profiles, _ = load_profiles(str(slim))
    att = attribute(profiles[-1])
    assert att.scaled["transitions"] > 0.0, \
        "queryEnd ledger must back-fill the bucket"


# ---------------------------------------------------------------------------
# tools trace: Chrome trace-event format + CLI + unattributed check
# ---------------------------------------------------------------------------

def _validate_chrome_trace(trace):
    """The subset of the Trace Event Format spec Perfetto requires."""
    assert isinstance(trace, dict)
    assert isinstance(trace["traceEvents"], list)
    for ev in trace["traceEvents"]:
        assert ev["ph"] in ("M", "X", "C"), ev
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert "name" in ev["args"]
        elif ev["ph"] == "X":
            assert isinstance(ev["name"], str) and ev["name"]
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
        elif ev["ph"] == "C":
            assert ev["ts"] >= 0 and isinstance(ev["args"], dict)
    # must survive a strict JSON round trip (what the UI actually loads)
    assert json.loads(render_trace(trace)) == json.loads(
        json.dumps(trace, default=str))


def test_trace_export_is_valid_chrome_trace(tmp_path):
    log = tmp_path / "trace.jsonl"
    _run_logged_query(log)
    trace, unattributed, _ = trace_from_log(str(log))
    assert unattributed == 0
    _validate_chrome_trace(trace)
    evs = trace["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert any(e["cat"] == "plan" for e in slices)
    assert any(e["cat"] == "hostTransition" for e in slices)
    # thread metadata names the transitions track
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               and e["args"]["name"] == "transitions" for e in evs)


def test_trace_cli_roundtrip_and_check(tmp_path, capsys):
    log = tmp_path / "cli.jsonl"
    _run_logged_query(log)
    out = tmp_path / "trace.json"
    rc = CLI.main(["trace", str(log), "-o", str(out), "--check"])
    assert rc == 0
    _validate_chrome_trace(json.loads(out.read_text()))
    capsys.readouterr()
    # an unattributed transition (query_id -1) fails --check
    bad = tmp_path / "bad.jsonl"
    bad.write_text(_jline("hostTransition", EV.NO_QUERY, -1, 1.0,
                          direction="h2d", bytes=10,
                          duration_s=0.01) + "\n")
    assert CLI.main(["trace", str(bad), "-o",
                     str(tmp_path / "bad.json"), "--check"]) == 1
    err = capsys.readouterr().err
    assert "unattributed" in err


def test_unattributed_counter_counts_only_orphans(tmp_path):
    log = tmp_path / "mix.jsonl"
    log.write_text("\n".join([
        _jline("queryStart", 1, 1, 1.0, description="q"),
        _jline("hostTransition", 1, -1, 1.5, direction="d2h",
               bytes=8, duration_s=0.001),
        _jline("deviceSync", EV.NO_QUERY, -1, 1.6, site="stray",
               duration_s=0.002),
        _jline("queryEnd", 1, 1, 2.0, duration_s=1.0),
    ]) + "\n")
    events, _ = read_events(str(log))
    assert unattributed_transitions(events) == 1


def test_trace_empty_profiles_still_valid():
    _validate_chrome_trace(build_trace([]))


# ---------------------------------------------------------------------------
# serving latency histograms in the Prometheus exposition
# ---------------------------------------------------------------------------

def test_latency_histogram_buckets_cumulative():
    from spark_rapids_tpu.serving.server import (LATENCY_BUCKETS,
                                                 LatencyHistogram)
    h = LatencyHistogram()
    for v in (0.0005, 0.003, 0.003, 0.08, 7.0, 1e9):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert abs(snap["sum"] - (0.0005 + 0.003 + 0.003 + 0.08 + 7.0 + 1e9)
               ) < 1e-6
    les = [le for le, _ in snap["buckets"]]
    assert les == sorted(les) and les[-1] == math.inf
    counts = [c for _, c in snap["buckets"]]
    assert counts == sorted(counts), "cumulative counts must be monotone"
    assert counts[-1] == snap["count"], "+Inf bucket equals _count"
    assert LATENCY_BUCKETS[-1] == math.inf


def test_prometheus_serving_histogram_exposition():
    from spark_rapids_tpu.serving import server as SRV
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    SRV.observe_latency("e2e", 0.042)
    SRV.observe_latency("e2e", 3.5)
    SRV.observe_latency("plan", 0.002)
    text = EV.render_prometheus()
    fam = "spark_rapids_tpu_serving_latency_seconds"
    assert f"# TYPE {fam} histogram" in text
    stage_series = {}
    for line in text.splitlines():
        if line.startswith(fam + "_bucket{"):
            labels, value = line.rsplit(" ", 1)
            stage = labels.split('stage="')[1].split('"')[0]
            le = labels.split('le="')[1].split('"')[0]
            stage_series.setdefault(stage, []).append((le, float(value)))
    assert "e2e" in stage_series and "plan" in stage_series
    for stage, series in stage_series.items():
        counts = [c for _, c in series]
        assert counts == sorted(counts), \
            f"{stage}: cumulative bucket counts must be monotone"
        assert series[-1][0] == "+Inf"
        # _count equals the +Inf bucket, _sum present
        cnt = [ln for ln in text.splitlines()
               if ln.startswith(f'{fam}_count{{stage="{stage}"}}')]
        assert cnt and float(cnt[0].rsplit(" ", 1)[1]) == counts[-1]
        assert any(ln.startswith(f'{fam}_sum{{stage="{stage}"}}')
                   for ln in text.splitlines())


def test_prometheus_transition_counters_present():
    tpu_session({"spark.rapids.sql.test.enabled": "false"})
    TR.record_h2d(64, 0.001)
    text = EV.render_prometheus()
    for name in ("h2d_transitions_total", "h2d_bytes_total",
                 "d2h_transitions_total", "d2h_bytes_total",
                 "device_syncs_total"):
        assert f"spark_rapids_tpu_{name}" in text, name


def test_serving_stage_decomposition_rides_admission_event(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.serving import QueryServer
    from spark_rapids_tpu.serving.server import STAGE_KEYS
    rng = np.random.default_rng(5)
    t = pa.table({"k": rng.integers(0, 5, 2000).astype(np.int64),
                  "v": rng.standard_normal(2000)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    s.create_or_replace_temp_view("t", s.read.parquet(path))
    # completion events fire OUTSIDE any query scope; a global ring sink
    # is where they land (the live sampler registers one the same way)
    ring = EV.RingBufferSink(capacity=256)
    EV.add_global_sink(ring)
    try:
        srv = QueryServer(session=s)
        try:
            sub = srv.submit("SELECT k, SUM(v) AS s FROM t GROUP BY k "
                             "ORDER BY k")
            sub.result(120)
        finally:
            srv.stop()
    finally:
        EV.remove_global_sink(ring)
    stages = sub.info["stages"]
    assert set(stages) == set(STAGE_KEYS)
    assert all(v >= 0.0 for v in stages.values())
    assert stages["plan_s"] > 0.0 and stages["execute_s"] >= 0.0
    # the complete servingAdmission event carries the decomposition
    done = [e for e in ring.events() if e.kind == "servingAdmission"
            and e.payload.get("op") == "complete"]
    assert done, "completion must emit a servingAdmission event"
    pay = done[-1].payload
    assert pay["resolved"] == "planned"
    for k in STAGE_KEYS:
        assert k in pay and pay[k] >= 0.0


# ---------------------------------------------------------------------------
# bit-identity: instrumentation must never change results
# ---------------------------------------------------------------------------

def test_trimodal_bit_identity():
    """Same query under (events on, counters-only, fully disabled)
    produces bit-identical rows — the gateway observes, never
    perturbs."""
    modes = [
        {"spark.rapids.sql.transitions.enabled": "true",
         "spark.rapids.sql.transitions.events": "true"},
        {"spark.rapids.sql.transitions.enabled": "true",
         "spark.rapids.sql.transitions.events": "false"},
        {"spark.rapids.sql.transitions.enabled": "false"},
    ]
    results = []
    try:
        for extra in modes:
            conf = {"spark.rapids.sql.test.enabled": "false"}
            conf.update(extra)
            s = tpu_session(conf)
            from spark_rapids_tpu import functions as F
            from spark_rapids_tpu.expressions.base import Alias, col
            df = s.create_dataframe(_DATA, num_partitions=2)
            rows = (df.filter(col("v") > 0.0).group_by("k")
                    .agg(Alias(F.sum(col("v")), "sv"),
                         Alias(F.count(col("v")), "c"))
                    .sort("k").collect())
            results.append(rows)
    finally:
        tpu_session({"spark.rapids.sql.test.enabled": "false"})
    for rows in results[1:]:
        assert len(rows) == len(results[0])
        for a, b in zip(results[0], rows):
            assert a["k"] == b["k"] and a["c"] == b["c"]
            # bit identity, not approx: instrumentation is pure
            assert np.float64(a["sv"]).tobytes() == \
                np.float64(b["sv"]).tobytes()
