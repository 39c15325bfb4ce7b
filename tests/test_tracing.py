"""Query observability tests: span tree, event log round-trip, EXPLAIN
ANALYZE, event-hook fire-once contracts, Prometheus exposition, metric
reset (reference: Spark's SQL event log + GpuTaskMetrics accumulators +
the SQL UI execution graph)."""

import json
import os

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux import tracing as TR
from spark_rapids_tpu.aux.metrics import MetricLevel, collect_metrics
from spark_rapids_tpu.columnar import batch_from_pydict
from spark_rapids_tpu.expressions.base import Alias, col, lit

from tests.asserts import tpu_session

RNG = np.random.default_rng(11)


def _sales_dim_session(tmp_path):
    """join + aggregate + sort over parquet — the TPC-DS-class shape the
    acceptance criteria name."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    n = 4000
    sales = s.create_dataframe({
        "sk": RNG.integers(0, 50, n).astype(np.int64),
        "qty": RNG.integers(1, 9, n).astype(np.int64),
    }, num_partitions=2)
    pq = str(tmp_path / "sales.parquet")
    sales.write_parquet(pq)
    dim = s.create_dataframe({
        "sk": np.arange(50, dtype=np.int64),
        "name": np.array([f"item{i}" for i in range(50)], dtype=object),
    })
    df = (s.read.parquet(pq)
          .join(dim, on="sk")
          .group_by("name").agg(Alias(F.sum(col("qty")), "q"))
          .order_by("q", ascending=False))
    return s, df


def test_explain_analyze_join_agg_sort(tmp_path):
    s, df = _sales_dim_session(tmp_path)
    text = df.explain(analyze=True)
    assert "== Analyzed Plan" in text
    assert "== Query Summary ==" in text
    # per-node annotations on a real multi-exec tree
    assert "rows=" in text and "batches=" in text and "opTime=" in text
    assert "Agg" in text and "Join" in text and "Sort" in text
    # the run published a summary with task attribution
    qm = TR.last_query_summary()
    assert qm is not None and qm["tasks"] > 0
    assert qm["nodes"], "summary must carry per-node metrics"
    total_rows = sum(n.get("numOutputRows", 0) for n in qm["nodes"])
    assert total_rows > 0


def test_span_tree_mirrors_plan(tmp_path):
    s, df = _sales_dim_session(tmp_path)
    with TR.QueryExecution(description="unit") as qe:
        plan = df._executed_plan()
        for _ in plan.execute_all():
            pass
    execs = [sp for sp in qe._exec_spans()]
    plan_nodes = plan.collect_nodes()
    # reused exchange subtrees may collapse copies onto one metrics dict;
    # every span still corresponds to a plan node and vice versa
    assert len(execs) == len(plan_nodes)
    by_name = {sp.name for sp in execs}
    assert {n.name for n in plan_nodes} == by_name
    # partition child spans exist under executed nodes
    parts = [c for sp in execs for c in sp.children
             if c.kind == "partition"]
    assert parts, "execution must open partition spans"
    assert all(p.end is not None for p in parts)


def test_event_log_roundtrip(tmp_path):
    """Tier-1 schema pin: every emitted event parses and carries
    query_id/span_id plus monotonic timestamps."""
    log = tmp_path / "events.jsonl"
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.sql.eventLog.path": str(log)})
    df = s.create_dataframe(
        {"k": RNG.integers(0, 7, 2000), "v": RNG.standard_normal(2000)},
        num_partitions=2)
    df.group_by("k").agg(Alias(F.sum(col("v")), "sv")).collect()
    df.count()
    lines = log.read_text().splitlines()
    assert lines, "event log must not be empty"
    # a fresh file opens with the schema-version header line
    head = json.loads(lines[0])
    assert head["event"] == "eventLogHeader"
    assert head["v"] == EV.EVENT_SCHEMA_VERSION
    kinds = set()
    last_ts = {}
    for line in lines:
        ev = EV.parse_event_line(line)   # raises on schema drift
        raw = json.loads(line)
        for key in ("event", "query_id", "span_id", "ts", "v"):
            assert key in raw, f"event missing {key}: {line}"
        if ev.kind == "eventLogHeader":
            assert raw["query_id"] == EV.NO_QUERY
            continue
        assert raw["query_id"] > 0
        assert raw["span_id"] > 0
        assert isinstance(raw["ts"], float)
        # timestamps are monotonic within each query
        assert raw["ts"] >= last_ts.get(raw["query_id"], 0.0)
        last_ts[raw["query_id"]] = raw["ts"]
        kinds.add(ev.kind)
    assert {"queryStart", "queryEnd", "spanMetrics", "taskEnd"} <= kinds
    assert len(last_ts) >= 2, "both actions must be logged"


def test_spill_and_retry_events_fire_once_each(tmp_path):
    from spark_rapids_tpu.memory import retry as R
    from spark_rapids_tpu.memory.catalog import BufferCatalog

    def make_batch(seed):
        rng = np.random.default_rng(seed)
        return batch_from_pydict({
            "a": rng.integers(0, 1000, 2048).astype(np.int64),
            "b": rng.standard_normal(2048),
        }).to_device()

    cat = BufferCatalog(device_limit_bytes=1 << 20,
                        host_limit_bytes=1 << 30,
                        disk_dir=str(tmp_path))
    with TR.QueryExecution(description="unit-hooks") as qe:
        handles = [cat.add_device_batch(make_batch(i)) for i in range(4)]
        before = cat.spill_count
        cat.synchronous_spill(None)       # push everything spillable off
        spills = [e for e in qe.events() if e.kind == "spill"]
        assert len(spills) == cat.spill_count - before, \
            "exactly one spill event per spilled buffer"
        assert all(e.payload["bytes"] > 0 for e in spills)
        assert all(e.payload["tier"] == "device->host" for e in spills)
        # retry hook: one event per injected-and-retried OOM
        R.force_retry_oom(2)
        R.with_retry_no_split(None, lambda: R.maybe_inject_oom() or 1)
        retries = [e for e in qe.events() if e.kind == "retryOOM"]
        assert len(retries) == 2
        for h in handles:
            cat.remove(h)
    # events got the query's id stamped
    assert all(e.query_id == qe.query_id for e in qe.events())
    summary = qe.summary_dict
    assert summary is not None and summary["status"] == "ok"


def test_split_retry_event_fires_once(tmp_path):
    from spark_rapids_tpu.memory import retry as R
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.memory.spillable import SpillableColumnarBatch

    cat = BufferCatalog(device_limit_bytes=8 << 20,
                        host_limit_bytes=1 << 30, disk_dir=str(tmp_path))
    hb = batch_from_pydict({"a": np.arange(1000, dtype=np.int64)})
    with TR.QueryExecution(description="unit-split") as qe:
        sb = SpillableColumnarBatch.from_host(hb, catalog=cat)
        R.force_split_and_retry_oom(1)
        out = list(R.with_retry(sb, lambda s: R.maybe_inject_oom()
                                or s.row_count))
        assert sum(out) == 1000
        splits = [e for e in qe.events() if e.kind == "splitRetry"]
        assert len(splits) == 1
        assert splits[0].payload["pieces"] == 2


def test_injected_retry_attributed_to_query(tmp_path):
    """Acceptance shape: a forced RetryOOM during a query shows up both
    as events in the JSONL log and as a nonzero retry_count in the query
    summary."""
    from spark_rapids_tpu.exec import aggregate as AG
    log = tmp_path / "ev.jsonl"
    s = tpu_session({
        "spark.rapids.sql.test.enabled": "false",
        "spark.rapids.sql.test.injectRetryOOM": "true",
        "spark.rapids.sql.test.agg.forceMergeRepartitionDepth": "1",
        "spark.rapids.sql.eventLog.path": str(log),
    })
    try:
        df = s.create_dataframe(
            {"k": RNG.integers(0, 50, 5000), "v": RNG.integers(0, 9, 5000)},
            num_partitions=2)
        rows = df.group_by("k").agg(Alias(F.sum(col("v")), "s")).collect()
        assert len(rows) == 50
        qm = TR.last_query_summary()
        assert qm is not None and qm["retry_count"] > 0, \
            "query summary must attribute the injected retries"
        events = [json.loads(ln) for ln in log.read_text().splitlines()]
        assert any(e["event"] == "retryOOM" for e in events)
        assert any(e["event"] == "taskEnd" and e.get("retry_count", 0) > 0
                   for e in events)
    finally:
        AG.FORCE_REPARTITION_BELOW_DEPTH = 0
        from spark_rapids_tpu.plan.base import set_task_oom_injection
        set_task_oom_injection("false")


def test_metrics_reset_between_actions():
    """Re-run staleness fix: repeated actions on the same DataFrame report
    per-query metrics, not accumulated ones."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = (s.create_dataframe({"a": np.arange(1000, dtype=np.int64)})
          .select(Alias(col("a") + lit(1), "b")))
    plan1 = df._executed_plan()
    plan1.collect_host()
    m1 = collect_metrics(plan1)
    plan2 = df._executed_plan()
    plan2.collect_host()
    m2 = collect_metrics(plan2)
    by_node1 = {m["node"]: m.get("numOutputBatches") for m in m1}
    by_node2 = {m["node"]: m.get("numOutputBatches") for m in m2}
    assert by_node1 == by_node2, \
        "second action must not accumulate on top of the first"


def test_metrics_level_validated_at_set_conf():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.sql.metrics.level", "bogus")
    with pytest.raises(ValueError):
        C.TpuConf({"spark.rapids.sql.metrics.level": "bogus"})
    with pytest.raises(ValueError):
        MetricLevel.parse("bogus")
    assert MetricLevel.parse(" debug ") is MetricLevel.DEBUG
    # valid values still round-trip through set_conf
    s.set_conf("spark.rapids.sql.metrics.level", "ESSENTIAL")


def test_render_prometheus_parses():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    s.create_dataframe({"a": np.arange(100, dtype=np.int64)}).count()
    text = EV.render_prometheus()
    assert "# TYPE spark_rapids_tpu_retry_total counter" in text
    assert "spark_rapids_tpu_device_pool_limit_bytes" in text
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        assert name.startswith("spark_rapids_tpu_")
        float(value)   # every sample parses


def test_ring_buffer_bounds_and_counts_drops():
    ring = EV.RingBufferSink(capacity=4)
    for i in range(10):
        ring.emit(EV.Event("x", 1, 1, float(i), {"i": i}))
    evs = ring.events()
    assert len(evs) == 4
    assert ring.dropped == 6
    assert [e.payload["i"] for e in evs] == [6, 7, 8, 9]


def test_emit_without_query_routes_to_global_sink():
    ring = EV.RingBufferSink()
    EV.add_global_sink(ring)
    try:
        EV.emit("heartbeatish", executor_id="exec-1")
        assert len(ring) == 1
        ev = ring.events()[0]
        assert ev.query_id == EV.NO_QUERY
        assert ev.payload["executor_id"] == "exec-1"
    finally:
        EV.remove_global_sink(ring)
    # and with neither query nor sink, emit is a no-op
    EV.emit("dropped-on-floor")


def test_tracing_disabled_by_conf():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.rapids.tpu.tracing.enabled": "false"})
    marker = TR.last_query_summary()
    df = s.create_dataframe({"a": np.arange(10, dtype=np.int64)})
    df.collect()
    assert TR.last_query_summary() is marker, \
        "disabled tracing must not publish summaries"


def test_heartbeat_events_attributed():
    from spark_rapids_tpu.shuffle.heartbeat import ShuffleHeartbeatManager
    clock = [0.0]
    mgr = ShuffleHeartbeatManager(timeout_s=5.0, clock=lambda: clock[0])
    with TR.QueryExecution(description="hb") as qe:
        mgr.register_executor("e1")
        mgr.register_executor("e2")
        clock[0] = 10.0
        dead = mgr.expire_dead()
        assert sorted(dead) == ["e1", "e2"]
        kinds = [e.kind for e in qe.events()]
        assert kinds.count("executorRegistered") == 2
        assert kinds.count("executorLost") == 2
