"""Adaptive shuffle reader tests (reference: GpuCustomShuffleReaderExec +
aqe_test.py)."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.exec.adaptive import (AdaptiveShuffleReaderExec,
                                            CoalescedPartitionSpec,
                                            PartialPartitionSpec,
                                            coalesce_specs, detect_skew,
                                            skew_split_specs)
from spark_rapids_tpu.expressions.base import Alias, col, lit

from tests.asserts import (assert_tpu_and_cpu_are_equal_collect, cpu_session,
                           tpu_session)


def test_coalesce_specs_merges_small():
    sizes = [10, 10, 10, 100, 5, 5, 5, 5]
    specs = coalesce_specs(sizes, target_bytes=30)
    # every input partition covered exactly once, in order
    covered = [p for s in specs for p in range(s.start, s.end)]
    assert covered == list(range(8))
    assert len(specs) < 8
    assert all(isinstance(s, CoalescedPartitionSpec) for s in specs)


def test_coalesce_specs_degenerate():
    assert coalesce_specs([], 10) == [CoalescedPartitionSpec(0, 1)]
    assert coalesce_specs([1000], 10) == [CoalescedPartitionSpec(0, 1)]


def test_detect_skew():
    sizes = [10, 10, 10, 10_000_000_000, 10]
    assert detect_skew(sizes, factor=5.0, min_bytes=1000) == [3]
    assert detect_skew([10, 10, 10], factor=5.0, min_bytes=1000) == []


def test_reader_end_to_end_differential():
    rng = np.random.default_rng(2)
    data = {"g": rng.integers(0, 100, 20_000).astype(np.int64),
            "v": rng.standard_normal(20_000)}
    # tiny advisory size: the 16 default shuffle partitions coalesce
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(data, num_partitions=4)
        .group_by("g").agg(Alias(F.sum(col("v")), "sv")),
        ignore_order=True, approx_float=True,
        conf={"spark.sql.adaptive.advisoryPartitionSizeInBytes": "8k"})


def test_reader_coalesces_partitions():
    rng = np.random.default_rng(3)
    data = {"g": rng.integers(0, 50, 5000).astype(np.int64),
            "v": rng.standard_normal(5000)}
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     "spark.sql.adaptive.advisoryPartitionSizeInBytes":
                         "1g"})
    df = (s.create_dataframe(data, num_partitions=4)
          .group_by("g").agg(Alias(F.count(col("v")), "c")))
    plan = df._executed_plan()
    readers = [n for n in plan.collect_nodes()
               if isinstance(n, AdaptiveShuffleReaderExec)]
    assert readers
    rows = plan.collect_host().row_count
    assert rows == 50
    # with a huge advisory size everything coalesces into few partitions
    assert readers[0].num_partitions < readers[0].children[0].num_partitions


def test_order_preserved_through_coalescing():
    rng = np.random.default_rng(4)
    data = {"v": rng.standard_normal(8000)}
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(data, num_partitions=4).order_by("v"),
        conf={"spark.sql.adaptive.advisoryPartitionSizeInBytes": "16k"})


def test_skew_split_specs_cover_batches():
    s = cpu_session()
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.plan.partitioning import RoundRobinPartitioning
    df = s.create_dataframe({"v": np.arange(100)}, num_partitions=5)
    ex = CpuShuffleExchangeExec(RoundRobinPartitioning(2), df._plan)
    specs = skew_split_specs(ex, 0, target_bytes=1)
    assert all(isinstance(x, PartialPartitionSpec) for x in specs)
    n_batches = len(ex._store[0])
    covered = [b for x in specs for b in range(x.batch_start, x.batch_end)]
    assert covered == list(range(n_batches))
    # reading the split specs yields every row of the partition
    reader = AdaptiveShuffleReaderExec(ex, specs=specs)
    rows = sum(b.row_count for p in range(reader.num_partitions)
               for b in reader.execute_partition(p))
    want = sum(b.row_count for b in ex._store[0])
    assert rows == want


# -- exchange reuse (Spark ReuseExchange; GpuOverrides updateForAdaptivePlan)

def test_exchange_reuse_dedups_identical_subtrees():
    import numpy as np
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from tests.asserts import tpu_session
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    rng = np.random.default_rng(8)
    df = s.create_dataframe({"k": rng.integers(0, 20, 4000),
                             "v": rng.integers(0, 9, 4000)},
                            num_partitions=3)
    agg = df.group_by("k").agg(F.sum("v").alias("sv"))
    u = agg.union_all(agg) if hasattr(agg, "union_all") else agg.union(agg)
    plan = TpuOverrides(s.conf).apply(u._plan)
    exchanges = plan.collect_nodes(
        lambda n: isinstance(n, CpuShuffleExchangeExec))
    assert len(exchanges) >= 2
    assert len({id(e) for e in exchanges}) < len(exchanges), \
        "identical exchange subtrees were not reused"
    rows = sorted((r["k"], r["sv"]) for r in u.collect())
    assert len(rows) == 40  # 20 groups x 2 branches


def test_exchange_reuse_respects_differences():
    import numpy as np
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.expressions.base import col, lit
    from spark_rapids_tpu.expressions import predicates as P
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from tests.asserts import tpu_session
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    rng = np.random.default_rng(8)
    df = s.create_dataframe({"k": rng.integers(0, 20, 4000),
                             "v": rng.integers(0, 9, 4000)},
                            num_partitions=3)
    a = df.filter(P.GreaterThan(col("v"), lit(2))) \
        .group_by("k").agg(F.sum("v").alias("sv"))
    b = df.filter(P.GreaterThan(col("v"), lit(5))) \
        .group_by("k").agg(F.sum("v").alias("sv"))
    u = a.union(b) if not hasattr(a, "union_all") else a.union_all(b)
    plan = TpuOverrides(s.conf).apply(u._plan)
    exchanges = plan.collect_nodes(
        lambda n: isinstance(n, CpuShuffleExchangeExec))
    assert len({id(e) for e in exchanges}) == len(exchanges), \
        "differing subtrees must not share an exchange"


def test_coordinated_join_side_coalescing():
    """Both sides of a shuffled join read through ONE coordinated spec:
    tiny shuffle partitions coalesce identically on both sides (pairing
    preserved) and the join result matches the oracle."""
    import numpy as np
    from spark_rapids_tpu.exec.adaptive import AdaptiveShuffleReaderExec
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    from tests.asserts import (assert_tpu_and_cpu_are_equal_collect,
                               tpu_session)
    rng = np.random.default_rng(3)
    da = {"k": rng.integers(0, 40, 3000), "v": rng.integers(0, 9, 3000)}
    db = {"k": rng.integers(0, 40, 2000), "w": rng.integers(0, 9, 2000)}

    def q(s):
        # a SHUFFLED join: Spark's size rule would broadcast these few KB
        s.set_conf("spark.sql.autoBroadcastJoinThreshold", "-1")
        a = s.create_dataframe(da, num_partitions=4)
        b = s.create_dataframe(db, num_partitions=4)
        return a.join(b, on="k")

    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    plan = TpuOverrides(s.conf).apply(q(s)._plan)
    readers = plan.collect_nodes(
        lambda n: isinstance(n, AdaptiveShuffleReaderExec))
    shared = [r for r in readers if r._shared is not None]
    assert len(shared) >= 2, "join sides did not get coordinated readers"
    assert shared[0]._shared is shared[1]._shared
    # the shared specs must reference the IN-TREE exchanges (a later
    # tree transform copying them apart would double-materialize every
    # shuffled join -- found in review)
    in_tree = {id(r.children[0]) for r in shared}
    assert {id(e) for e in shared[0]._shared._exs} == in_tree
    # tiny partitions genuinely coalesce (4 -> 1 on both sides)
    assert shared[0].num_partitions == 1
    assert shared[1].num_partitions == 1
    rows = plan.collect_host().to_pydict()
    assert rows and len(next(iter(rows.values()))) > 0
