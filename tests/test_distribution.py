"""SPMD partitioned execution: sharding-aware planning (exchange
elision), the in-mesh shard_map exchange, and mesh-aware AQE.

Covers the PR's acceptance contract: co-partitioned join / agg plans
show ZERO redundant exchanges, verified bit-identical against the CPU
oracle AND the single-device path AND the 8-virtual-device mesh; the
pass disabled reproduces today's plans exactly (tree_string-pinned);
mesh conf validates at set_conf; the ICI path falls back host-staged
when the working set exceeds per-device HBM; and AQE aligns coalesced
partition counts to mesh multiples."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.exchange import (CpuShuffleExchangeExec,
                                            TpuShuffleExchangeExec)
from spark_rapids_tpu.parallel.mesh import (active_mesh, data_mesh,
                                            set_active_mesh)
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.session import TpuSession

from tests.asserts import cpu_session, tpu_session


def _rows(df):
    return sorted(map(str, df.collect()))


def _exchange_ids(plan):
    return {id(n) for n in plan.collect_nodes()
            if isinstance(n, CpuShuffleExchangeExec)}


@pytest.fixture
def no_mesh():
    """Guards against a leaked active mesh in either direction."""
    set_active_mesh(None)
    yield
    set_active_mesh(None)


@pytest.fixture
def mesh8():
    set_active_mesh(data_mesh(8))
    yield active_mesh()
    set_active_mesh(None)


def _join_data(rng=None):
    rng = rng or np.random.default_rng(7)
    left = {"k": rng.integers(0, 40, 3000).astype(np.int64),
            "v": rng.integers(0, 9, 3000).astype(np.int64)}
    right = {"k": rng.integers(0, 40, 2000).astype(np.int64),
             "w": rng.integers(0, 9, 2000).astype(np.int64)}
    return left, right


def _copart_join(s, n=4):
    left, right = _join_data()
    a = s.create_dataframe(left, num_partitions=n).repartition(n, "k")
    b = s.create_dataframe(right, num_partitions=n).repartition(n, "k")
    return a.join(b, on="k")


def _agg_above_join(s, n=4):
    # the subject is a SHUFFLED join: Spark's size rule would broadcast
    # tables of a few KB (tests/test_partitioned_store.py has that rule)
    s.set_conf("spark.sql.autoBroadcastJoinThreshold", "-1")
    left, right = _join_data()
    a = s.create_dataframe(left, num_partitions=n)
    b = s.create_dataframe(right, num_partitions=n)
    return (a.join(b, on="k").group_by("k")
            .agg(F.sum("v").alias("sv"), F.sum("w").alias("sw")))


# ---------------------------------------------------------------------------
# elision: plan shape
# ---------------------------------------------------------------------------

def test_copartitioned_join_elides_both_exchanges(no_mesh):
    """repartition(k) -> join(k): the join's own exchanges are redundant
    and vanish; only the two repartition producers remain."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    ov = TpuOverrides(s.conf)
    final = ov.apply(_copart_join(s)._plan)
    assert len(ov.last_elided) == 2, \
        [e.desc() for e in ov.last_elided]
    assert len(_exchange_ids(final)) == 2, final.tree_string()


def test_agg_above_join_elides_exchange(no_mesh):
    """The aggregate above a shuffled join re-shuffled the join output
    over the very same key: elided."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    ov = TpuOverrides(s.conf)
    final = ov.apply(_agg_above_join(s)._plan)
    assert len(ov.last_elided) == 1
    # the two join exchanges stay (scans deliver nothing)
    assert len(_exchange_ids(final)) == 2, final.tree_string()


def test_repeated_repartition_same_keys_elides(no_mesh):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    left, _ = _join_data()
    df = (s.create_dataframe(left, num_partitions=4)
          .repartition(4, "k").repartition(4, "k"))
    ov = TpuOverrides(s.conf)
    final = ov.apply(df._plan)
    assert len(ov.last_elided) == 1
    assert len(_exchange_ids(final)) == 1


@pytest.mark.parametrize("variant", ["different_keys", "different_n",
                                     "round_robin"])
def test_non_redundant_exchanges_stay(no_mesh, variant):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    left, _ = _join_data()
    df = s.create_dataframe(left, num_partitions=4)
    if variant == "different_keys":
        df = df.repartition(4, "k").repartition(4, "v")
    elif variant == "different_n":
        df = df.repartition(4, "k").repartition(3, "k")
    else:
        df = df.repartition(4).repartition(4)
    ov = TpuOverrides(s.conf)
    final = ov.apply(df._plan)
    assert not ov.last_elided
    assert len(_exchange_ids(final)) == 2, final.tree_string()


def test_disabled_is_an_exact_noop(no_mesh, monkeypatch):
    """spark.rapids.sql.distribution.enabled=false reproduces today's
    plans EXACTLY: its tree_string equals the enabled pipeline with the
    elision pass neutralized to identity — the flag's only effect is
    whether the pass runs."""
    import spark_rapids_tpu.plan.distribution as DIST
    q_off = _agg_above_join(tpu_session(
        {"spark.rapids.sql.test.enabled": "false",
         "spark.rapids.sql.distribution.enabled": "false"}))
    off_tree = TpuOverrides(q_off._session.conf) \
        .apply(q_off._plan).tree_string()
    s_on = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    q_on = _agg_above_join(s_on)
    monkeypatch.setattr(DIST, "eliminate_redundant_exchanges",
                        lambda plan: (plan, []))
    neutral_tree = TpuOverrides(s_on.conf).apply(q_on._plan).tree_string()
    assert off_tree == neutral_tree
    monkeypatch.undo()
    real_tree = TpuOverrides(s_on.conf).apply(q_on._plan).tree_string()
    assert real_tree != off_tree     # the pass genuinely does something
    assert "Exchange" in off_tree


# ---------------------------------------------------------------------------
# elision: bit identity (CPU oracle vs single-device vs mesh)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("build", [_copart_join, _agg_above_join],
                         ids=["copart_join", "agg_above_join"])
def test_elided_plans_trimodal_bit_identity(no_mesh, build):
    expect = _rows(build(cpu_session()))
    # single device
    single = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    assert _rows(build(single)) == expect
    # 8-device mesh: remaining exchanges ride the collective
    set_active_mesh(data_mesh(8))
    try:
        m = tpu_session({"spark.rapids.sql.test.enabled": "false",
                         "spark.rapids.debug.planCheck": "true"})
        df = build(m, n=8)
        ov = TpuOverrides(m.conf)
        final = ov.apply(df._plan)
        batch = final.collect_host()
        names = list(batch.to_pydict().keys())
        got = sorted(str(dict(zip(names, row)))
                     for row in zip(*batch.to_pydict().values()))
        assert ov.last_elided, "mesh plan elided nothing"
    finally:
        set_active_mesh(None)
    expect8 = _rows(build(cpu_session(), n=8))
    assert got == expect8


def test_mesh_join_with_elided_agg_uses_collective(no_mesh):
    """The flagship shape: join exchanges ride ICI, the agg exchange
    above the join is elided — partial AND final aggregation run on the
    join's device-resident shards with zero further movement."""
    expect = _rows(_agg_above_join(cpu_session(), n=8))
    set_active_mesh(data_mesh(8))
    try:
        s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
        df = _agg_above_join(s, n=8)
        ov = TpuOverrides(s.conf)
        final = ov.apply(df._plan)
        assert len(ov.last_elided) == 1
        batch = final.collect_host()
        exs = [n for n in final.collect_nodes()
               if isinstance(n, TpuShuffleExchangeExec)]
        assert exs and all(x._collective is not None for x in exs), \
            "join exchanges did not take the in-mesh path"
        names = list(batch.to_pydict().keys())
        got = sorted(str(dict(zip(names, row)))
                     for row in zip(*batch.to_pydict().values()))
    finally:
        set_active_mesh(None)
    assert got == expect


# ---------------------------------------------------------------------------
# events + EXPLAIN surfacing
# ---------------------------------------------------------------------------

def test_elision_event_and_explain_line(no_mesh):
    from spark_rapids_tpu.aux.events import (RingBufferSink,
                                             add_global_sink,
                                             remove_global_sink)
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = _agg_above_join(s)
    sink = RingBufferSink(256)
    add_global_sink(sink)
    try:
        # outside any query scope so emits route to the global sink
        TpuOverrides(s.conf).apply(df._plan).collect_host()
    finally:
        remove_global_sink(sink)
    evs = [e for e in sink.events() if e.kind == "exchangeElided"]
    assert evs and evs[0].payload["count"] == 1
    assert evs[0].payload["exchanges"]
    text = df.explain()
    assert "exchangeElided=1" in text


def test_ici_exchange_event_carries_shard_stats(no_mesh):
    from spark_rapids_tpu.aux.events import (RingBufferSink,
                                             add_global_sink,
                                             remove_global_sink)
    left, _ = _join_data()
    set_active_mesh(data_mesh(8))
    sink = RingBufferSink(256)
    add_global_sink(sink)
    try:
        s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
        df = (s.create_dataframe(left, num_partitions=8)
              .group_by("k").agg(F.sum("v").alias("sv")))
        TpuOverrides(s.conf).apply(df._plan).collect_host()
    finally:
        remove_global_sink(sink)
        set_active_mesh(None)
    evs = [e for e in sink.events() if e.kind == "iciExchange"]
    assert evs, "mesh group-by did not take the ICI exchange"
    p = evs[0].payload
    assert p["devices"] == 8
    assert len(p["shard_rows"]) == 8
    assert p["rows"] == sum(p["shard_rows"]) > 0
    assert p["duration_s"] >= 0.0


# ---------------------------------------------------------------------------
# spill-safe fallback: ICI vs host per stage
# ---------------------------------------------------------------------------

def test_hbm_exceeded_falls_back_host_staged(no_mesh, monkeypatch):
    """A working set that cannot fit per-device HBM must degrade to the
    host-staged (spillable) path — bit-identically, with the fallback
    recorded."""
    import spark_rapids_tpu.parallel.spmd as SPMD
    from spark_rapids_tpu.aux.events import (RingBufferSink,
                                             add_global_sink,
                                             remove_global_sink)
    left, _ = _join_data()

    def q(s):
        return (s.create_dataframe(left, num_partitions=8)
                .group_by("k").agg(F.sum("v").alias("sv")))

    expect = _rows(q(cpu_session()))
    monkeypatch.setattr(SPMD, "_hbm_budget", lambda: 64)
    set_active_mesh(data_mesh(8))
    sink = RingBufferSink(256)
    add_global_sink(sink)
    try:
        s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
        df = q(s)
        ov = TpuOverrides(s.conf)
        final = ov.apply(df._plan)
        batch = final.collect_host()
        names = list(batch.to_pydict().keys())
        got = sorted(str(dict(zip(names, row)))
                     for row in zip(*batch.to_pydict().values()))
        exs = [n for n in final.collect_nodes()
               if isinstance(n, TpuShuffleExchangeExec)]
        assert all(x._collective is None for x in exs), \
            "exchange took the ICI path despite the HBM guard"
    finally:
        remove_global_sink(sink)
        set_active_mesh(None)
    assert got == expect
    fb = [e for e in sink.events() if e.kind == "collectiveFallback"]
    assert fb and fb[0].payload["reason"] == "hbm"
    assert not [e for e in sink.events() if e.kind == "iciExchange"]


# ---------------------------------------------------------------------------
# mesh conf validation (spark.rapids.mesh.*)
# ---------------------------------------------------------------------------

def test_mesh_shape_format_validates_at_set_conf(no_mesh):
    s = cpu_session()
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.mesh.shape", "eight")
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.mesh.shape", "0")
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.mesh.axes", "data,,x")
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.mesh.axes", "data,data")
    # axes/shape arity mismatch is caught by the mesh sync at set_conf,
    # before any collective runs
    with pytest.raises(ValueError):
        s.set_conf("spark.rapids.mesh.shape", "2,4")
    # an EMPTY shape means 1-D: extra axis names raise instead of being
    # silently dropped when the mesh builds
    s2 = cpu_session()
    with pytest.raises(ValueError, match="1-D"):
        s2.set_conf("spark.rapids.mesh.axes", "data,model")


def test_mesh_shape_must_divide_device_count(no_mesh):
    s = cpu_session()
    s.set_conf("spark.rapids.mesh.shape", "3")
    with pytest.raises(ValueError, match="divid"):
        s.set_conf("spark.rapids.mesh.enabled", "true")
    assert active_mesh() is None


def test_mesh_conf_activates_and_emits_topology(no_mesh):
    from spark_rapids_tpu.aux.events import (RingBufferSink,
                                             add_global_sink,
                                             remove_global_sink)
    sink = RingBufferSink(64)
    add_global_sink(sink)
    try:
        TpuSession(TpuConf({"spark.rapids.sql.enabled": "false",
                            "spark.rapids.mesh.enabled": "true",
                            "spark.rapids.mesh.shape": "8"}),
                   init_device=False)
        ctx = active_mesh()
        assert ctx is not None and ctx.num_devices == 8
        assert ctx.data_axis == "data"
    finally:
        remove_global_sink(sink)
        set_active_mesh(None)
    evs = [e for e in sink.events() if e.kind == "meshTopology"]
    assert evs and evs[0].payload["devices"] == 8
    assert evs[0].payload["axes"] == ["data"]


def test_mesh_conf_disable_tears_down_conf_mesh(no_mesh):
    """Explicit set_conf disable deactivates a conf-activated mesh;
    a default-conf session INIT does not clobber it (the interleaved-
    session discipline)."""
    s = TpuSession(TpuConf({"spark.rapids.sql.enabled": "false",
                            "spark.rapids.mesh.enabled": "true"}),
                   init_device=False)
    try:
        assert active_mesh() is not None
        # an unrelated default-conf session leaves the conf mesh alone
        TpuSession(TpuConf({"spark.rapids.sql.enabled": "false"}),
                   init_device=False)
        assert active_mesh() is not None
        s.set_conf("spark.rapids.mesh.enabled", "false")
        assert active_mesh() is None
    finally:
        set_active_mesh(None)


def test_mesh_disabled_leaves_manual_mesh_alone(no_mesh):
    ctx = data_mesh(4)
    set_active_mesh(ctx)
    try:
        TpuSession(TpuConf({"spark.rapids.sql.enabled": "false"}),
                   init_device=False)
        assert active_mesh() is ctx
    finally:
        set_active_mesh(None)


# ---------------------------------------------------------------------------
# mesh-aware AQE
# ---------------------------------------------------------------------------

def test_coalesce_specs_align_snaps_to_multiple():
    from spark_rapids_tpu.exec.adaptive import (CoalescedPartitionSpec,
                                                coalesce_specs)
    sizes = [10] * 16
    specs = coalesce_specs(sizes, target_bytes=1000, align=8)
    covered = [p for sp in specs for p in range(sp.start, sp.end)]
    assert covered == list(range(16))
    assert len(specs) % 8 == 0
    # align=1 keeps the plain greedy result
    assert len(coalesce_specs(sizes, target_bytes=1000)) == 1
    # fewer inputs than the alignment: plain greedy (unachievable)
    assert len(coalesce_specs([10, 10], target_bytes=1, align=8)) == 2
    assert all(isinstance(sp, CoalescedPartitionSpec) for sp in specs)
    # rounding UP past the input count floors to the largest achievable
    # multiple instead of giving up: 12 inputs on an 8-mesh round to 16
    # but snap to 8 (the review-confirmed silent-skip bug)
    for n in (12, 13):
        specs_n = coalesce_specs([100] * n, target_bytes=10, align=8)
        assert len(specs_n) == 8
        assert [p for sp in specs_n
                for p in range(sp.start, sp.end)] == list(range(n))


def test_balanced_contiguous_groups_cover_and_balance():
    from spark_rapids_tpu.exec.adaptive import _balanced_contiguous
    sizes = [100, 1, 1, 1, 100, 1, 1, 1]
    specs = _balanced_contiguous(sizes, 4)
    assert len(specs) == 4
    covered = [p for sp in specs for p in range(sp.start, sp.end)]
    assert covered == list(range(8))
    # zero-size degenerate still yields k non-empty groups
    specs0 = _balanced_contiguous([0, 0, 0, 0], 2)
    assert len(specs0) == 2
    assert [(-(-s.start), s.end) for s in specs0] == [(0, 1), (1, 4)]


def test_mesh_aligned_adaptive_reader_e2e(no_mesh):
    """A host-staged shuffle (16 partitions != 8-device mesh) under an
    active mesh coalesces to a MULTIPLE of the mesh size, and the
    aqeCoalesce event records the aligned decision."""
    from spark_rapids_tpu.aux.events import (RingBufferSink,
                                             add_global_sink,
                                             remove_global_sink)
    from spark_rapids_tpu.exec.adaptive import AdaptiveShuffleReaderExec
    rng = np.random.default_rng(3)
    data = {"k": rng.integers(0, 60, 6000).astype(np.int64),
            "v": rng.standard_normal(6000)}

    def q(s):
        return (s.create_dataframe(data, num_partitions=16)
                .repartition(16, "k")
                .group_by("k").agg(F.count("v").alias("c")))

    expect = _rows(q(cpu_session()))
    set_active_mesh(data_mesh(8))
    sink = RingBufferSink(256)
    add_global_sink(sink)
    try:
        s = tpu_session(
            {"spark.rapids.sql.test.enabled": "false",
             "spark.sql.adaptive.advisoryPartitionSizeInBytes": "1g"})
        df = q(s)
        final = TpuOverrides(s.conf).apply(df._plan)
        batch = final.collect_host()
        names = list(batch.to_pydict().keys())
        got = sorted(str(dict(zip(names, row)))
                     for row in zip(*batch.to_pydict().values()))
        readers = [n for n in final.collect_nodes()
                   if isinstance(n, AdaptiveShuffleReaderExec)]
        assert readers
        assert all(r.num_partitions % 8 == 0 for r in readers), \
            [r.num_partitions for r in readers]
    finally:
        remove_global_sink(sink)
        set_active_mesh(None)
    assert got == expect
    evs = [e for e in sink.events() if e.kind == "aqeCoalesce"]
    assert evs
    assert all(e.payload["mesh"] == 8 for e in evs)
    assert all(e.payload["aligned"] for e in evs)


def test_mesh_align_disabled_keeps_natural_counts(no_mesh):
    from spark_rapids_tpu.exec.adaptive import AdaptiveShuffleReaderExec
    rng = np.random.default_rng(3)
    data = {"k": rng.integers(0, 60, 6000).astype(np.int64),
            "v": rng.standard_normal(6000)}
    set_active_mesh(data_mesh(8))
    try:
        s = tpu_session(
            {"spark.rapids.sql.test.enabled": "false",
             "spark.rapids.sql.adaptive.meshAlign": "false",
             "spark.sql.adaptive.advisoryPartitionSizeInBytes": "1g"})
        df = (s.create_dataframe(data, num_partitions=16)
              .repartition(16, "k")
              .group_by("k").agg(F.count("v").alias("c")))
        final = TpuOverrides(s.conf).apply(df._plan)
        final.collect_host()
        readers = [n for n in final.collect_nodes()
                   if isinstance(n, AdaptiveShuffleReaderExec)]
        assert readers
        # huge advisory size: everything merges to ONE partition
        assert readers[0].num_partitions == 1
    finally:
        set_active_mesh(None)


# ---------------------------------------------------------------------------
# verifier + exec guard
# ---------------------------------------------------------------------------

def test_verify_distribution_consistency_clean_on_elided_plan(no_mesh):
    from spark_rapids_tpu.plan.verify import verify_plan
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    final = TpuOverrides(s.conf).apply(_copart_join(s)._plan)
    violations = verify_plan(final, s.conf, emit_events=False)
    assert [v for v in violations
            if v.check == "distribution-consistency"] == []


def _manual_join(nl, nr):
    import spark_rapids_tpu.ops.join_ops as J
    from spark_rapids_tpu.exec.joins import CpuShuffledHashJoinExec
    from spark_rapids_tpu.expressions.base import BoundReference
    from spark_rapids_tpu import types as T
    s = cpu_session()
    left, right = _join_data()
    lp = s.create_dataframe(left, num_partitions=nl)._plan
    rp = s.create_dataframe(right, num_partitions=nr)._plan
    key_l = BoundReference(0, T.LONG, True)
    key_r = BoundReference(0, T.LONG, True)
    return CpuShuffledHashJoinExec([key_l], [key_r], J.INNER, None,
                                   lp, rp)


def test_verify_catches_mispartitioned_join(no_mesh, conf):
    from spark_rapids_tpu.plan.verify import verify_plan
    violations = verify_plan(_manual_join(4, 2), conf,
                             emit_events=False)
    assert any(v.check == "distribution-consistency" and
               "4 vs 2" in v.detail for v in violations)


def test_verify_catches_missing_exchange(no_mesh, conf):
    """Equal partition counts but NO exchange and no delivered hash
    distribution: the join is silently mis-partitioned — caught."""
    from spark_rapids_tpu.plan.verify import verify_plan
    violations = verify_plan(_manual_join(4, 4), conf,
                             emit_events=False)
    assert any(v.check == "distribution-consistency" and
               "no exchange boundary" in v.detail
               for v in violations)


def test_join_exec_guard_raises_on_count_mismatch(no_mesh):
    join = _manual_join(4, 2)
    with pytest.raises(ValueError, match="not co-partitioned"):
        list(join.execute_partition(0))
