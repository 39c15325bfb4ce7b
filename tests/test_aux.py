"""Aux subsystem tests: metrics, profiler ranges, plan capture, dumps, CBO
(reference: GpuExec metric wiring, NvtxWithMetrics, DumpUtils,
ExecutionPlanCaptureCallback, CostBasedOptimizerSuite)."""

import os

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux.capture import (ExecutionPlanCaptureCallback,
                                          dump_batch, dump_on_error)
from spark_rapids_tpu.aux.metrics import (MetricLevel, collect_metrics,
                                          instrument_plan)
from spark_rapids_tpu.aux import profiler as PROF
from spark_rapids_tpu.expressions.base import Alias, col, lit

from tests.asserts import cpu_session, tpu_session

RNG = np.random.default_rng(9)
_DATA = {"a": RNG.integers(0, 100, 2000).astype(np.int64),
         "b": RNG.standard_normal(2000)}


def test_metrics_levels_and_collection():
    s = tpu_session({"spark.rapids.sql.metrics.level": "DEBUG"})
    df = (s.create_dataframe(_DATA, num_partitions=2)
          .filter(col("a") > lit(10))
          .select(Alias(col("a") + lit(1), "a1")))
    plan = df._executed_plan()
    rows = plan.collect_host().row_count
    ms = collect_metrics(plan)
    assert ms, "instrumented plan must report metrics"
    by_node = {m["node"]: m for m in ms}
    # filter+project fuses into one whole-stage node (fuse_device_stages)
    root = [m for m in ms
            if "Project" in m["node"] or "FusedStage" in m["node"]]
    assert root and root[0]["numOutputBatches"] >= 1
    assert any(m.get("opTime", 0) > 0 for m in ms)
    # essential-only level drops opTime
    s2 = tpu_session({"spark.rapids.sql.metrics.level": "ESSENTIAL"})
    plan2 = (s2.create_dataframe(_DATA).select(col("a"))._executed_plan())
    plan2.collect_host()
    ms2 = collect_metrics(plan2)
    assert all("opTime" not in m for m in ms2)


def test_plan_capture_callback():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    ExecutionPlanCaptureCallback.start_capture()
    try:
        (s.create_dataframe(_DATA).filter(col("a") > lit(5)).collect())
        plans = ExecutionPlanCaptureCallback.get_captured_plans()
        assert plans
        ExecutionPlanCaptureCallback.assert_contains("TpuFusedStageExec")
        with pytest.raises(AssertionError):
            ExecutionPlanCaptureCallback.assert_contains("NoSuchExec")
    finally:
        ExecutionPlanCaptureCallback.end_capture()


def test_dump_batch_and_dump_on_error(tmp_path):
    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    hb = batch_from_pydict({"x": [1, 2, 3]})
    p = dump_batch(hb, str(tmp_path / "repro"))
    assert os.path.exists(p)
    import pyarrow.parquet as pq
    assert pq.read_table(p).num_rows == 3

    def gen():
        yield hb
        raise RuntimeError("kernel exploded")

    it = dump_on_error(gen(), str(tmp_path / "err"))
    assert next(it) is hb
    with pytest.raises(RuntimeError, match="dumped to"):
        next(it)


def test_profiler_trace(tmp_path):
    prof = PROF.Profiler(str(tmp_path / "trace"))
    try:
        with prof.scoped():
            import jax.numpy as jnp
            (jnp.arange(10) * 2).block_until_ready()
    except Exception as e:  # noqa: BLE001 - profiler availability varies
        pytest.skip(f"jax profiler unavailable here: {e}")
    dumped = list(os.walk(tmp_path / "trace"))
    assert any(files for _, _, files in dumped), "trace produced no files"


def test_cbo_reverts_tiny_device_regions():
    """A tiny scan->project sandwich should stay on CPU under the CBO
    (transfer cost dominates); large inputs stay on device."""
    small = {"a": np.arange(10)}
    s = tpu_session({"spark.rapids.sql.optimizer.enabled": "true",
                     "spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(small).select(Alias(col("a") + lit(1), "a1"))
    ex = df.explain()
    assert "cost-based optimizer" in ex
    assert [r["a1"] for r in df.collect()] == list(range(1, 11))
    # heavy pipeline on a big input: the saving dominates, device kept
    from spark_rapids_tpu import functions as F
    big = {"a": RNG.integers(0, 10, 1_000_000).astype(np.int64),
           "v": RNG.standard_normal(1_000_000)}
    df2 = (s.create_dataframe(big, num_partitions=2)
           .group_by("a").agg(Alias(F.sum(col("v")), "sv")))
    assert "TpuHashAggregate" in df2.explain()


def test_cbo_off_by_default():
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe({"a": np.arange(10)}).select(col("a"))
    assert "cost-based optimizer" not in df.explain()
