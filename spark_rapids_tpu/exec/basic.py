"""Basic physical operators.

Reference: basicPhysicalOperators.scala (GpuProjectExec :350 tiered
projection, GpuFilterExec :795, GpuRangeExec), limit.scala, GpuUnionExec,
GpuSampleExec in GpuOverrides registrations; transitions
GpuRowToColumnarExec.scala / GpuColumnarToRowExec.scala / HostColumnarToGpu.scala.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, HostColumnarBatch,
                                             batch_from_arrow)
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression, TCol)
from spark_rapids_tpu.expressions.evaluator import (eval_exprs_cpu,
                                                    eval_exprs_tpu, _out_names)
from spark_rapids_tpu.plan.base import (Exec, LeafExec, UnaryExec,
                                        closing_source)


def _project_schema(exprs: Sequence[Expression]) -> T.StructType:
    names = _out_names(exprs)
    return T.StructType([T.StructField(n, e.data_type, e.nullable)
                         for n, e in zip(names, exprs)])


# ---------------------------------------------------------------------------
# Scans
# ---------------------------------------------------------------------------

class CpuInMemoryScanExec(LeafExec):
    """Scan over in-memory arrow batches, pre-split into partitions.

    Carries a device-column cache shared by every plan derived from the same
    source DataFrame: the first device action uploads each referenced column
    once, later actions (and later queries over the same DataFrame) reuse the
    device-resident columns — the TPU analog of a device-cached table
    (reference: shuffle/cache keep batches device-resident in
    RapidsBufferCatalog; here the scan itself is the resident tier).
    """

    def __init__(self, partitions: List[List[HostColumnarBatch]],
                 schema: T.StructType, col_indices=None, dev_cache=None):
        super().__init__()
        self.partitions = partitions
        self._schema = schema
        #: column subset (pruning); None = all
        self.col_indices = col_indices
        #: (pidx, batch_idx, col_ordinal_in_full_schema) -> DeviceColumn;
        #: shared across shallow copies / pruned clones of this scan
        self._dev_cache = {} if dev_cache is None else dev_cache

    @property
    def schema(self):
        if self.col_indices is None:
            return self._schema
        return T.StructType([self._schema.fields[i]
                             for i in self.col_indices])

    @property
    def num_partitions(self):
        return max(1, len(self.partitions))

    def with_pruned_columns(self, indices):
        base = self.col_indices or list(range(len(self._schema.fields)))
        if not indices and base:
            # a batch with zero columns loses its row count in arrow form;
            # keep the narrowest column so row semantics survive
            def width(i):
                dt = self.schema.fields[i].data_type
                npdt = getattr(dt, "np_dtype", None)
                if dt.is_nested or npdt is None:  # strings/nested: wide
                    return 64
                return npdt.itemsize
            indices = [min(range(len(base)), key=width)]
        return CpuInMemoryScanExec(self.partitions, self._schema,
                                   [base[i] for i in indices],
                                   self._dev_cache)

    def _host_batches(self, pidx):
        if pidx >= len(self.partitions):
            return
        for hb in self.partitions[pidx]:
            if self.col_indices is None:
                yield hb
            else:
                yield HostColumnarBatch(
                    [hb.columns[i] for i in self.col_indices],
                    hb.row_count,
                    None if hb.names is None else
                    [hb.names[i] for i in self.col_indices])

    def execute_partition(self, pidx):
        yield from self._host_batches(pidx)

    def node_desc(self):
        cols = "" if self.col_indices is None else \
            f", cols={list(self.col_indices)}"
        return f"InMemoryScan[{self.num_partitions}p{cols}]"


def upload_batches(batches):
    """Host->device upload with device admission (the semaphore is acquired
    before the first device use; released by run_task at task completion)."""
    from spark_rapids_tpu.memory.device_manager import get_runtime
    from spark_rapids_tpu.plan.base import closing_source
    rt = get_runtime()
    with closing_source(iter(batches)) as it:
        for hb in it:
            if rt is not None:
                rt.semaphore.acquire_if_necessary()
            yield hb.to_device()


class TpuInMemoryScanExec(CpuInMemoryScanExec):
    is_device = True

    def __init__(self, cpu: CpuInMemoryScanExec):
        super().__init__(cpu.partitions, cpu._schema, cpu.col_indices,
                         cpu._dev_cache)

    def execute_partition(self, pidx):
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.memory.device_manager import get_runtime
        if pidx >= len(self.partitions):
            return
        rt = get_runtime()
        indices = self.col_indices or \
            list(range(len(self._schema.fields)))
        for bi, hb in enumerate(self.partitions[pidx]):
            if rt is not None:
                rt.semaphore.acquire_if_necessary()
            def alive(i):
                dc = self._dev_cache.get((pidx, bi, i))
                if dc is None:
                    return False
                deleted = getattr(dc.data, "is_deleted", None)
                return not (deleted and deleted())

            missing = [i for i in indices if not alive(i)]
            if missing:
                sub = HostColumnarBatch(
                    [hb.columns[i] for i in missing], hb.row_count,
                    [str(i) for i in missing])
                dev = sub.to_device()
                for i, dc in zip(missing, dev.columns):
                    self._dev_cache[(pidx, bi, i)] = dc
            names = None if hb.names is None else \
                [hb.names[i] for i in indices]
            yield ColumnarBatch(
                [self._dev_cache[(pidx, bi, i)] for i in indices],
                hb.row_count, names)

    def node_desc(self):
        cols = "" if self.col_indices is None else \
            f", cols={list(self.col_indices)}"
        return f"TpuInMemoryScan[{self.num_partitions}p{cols}]"


# ---------------------------------------------------------------------------
# Project / Filter
# ---------------------------------------------------------------------------

class CpuProjectExec(UnaryExec):
    def __init__(self, exprs: Sequence[Expression], child: Exec):
        super().__init__(child)
        self.exprs = list(exprs)

    @property
    def schema(self):
        return _project_schema(self.exprs)

    def execute_partition(self, pidx):
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                yield eval_exprs_cpu(self.exprs, b)

    def node_desc(self):
        return f"Project[{', '.join(e.sql() for e in self.exprs)}]"


class TpuProjectExec(UnaryExec):
    """Whole-stage-fused device projection (reference: GpuProjectExec with
    tiered project; here the whole expr list is one XLA program)."""

    is_device = True

    def __init__(self, exprs: Sequence[Expression], child: Exec):
        super().__init__(child)
        self.exprs = list(exprs)

    @property
    def schema(self):
        return _project_schema(self.exprs)

    def execute_partition(self, pidx):
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                yield eval_exprs_tpu(self.exprs, b)

    def node_desc(self):
        return f"TpuProject[{', '.join(e.sql() for e in self.exprs)}]"


class CpuFilterExec(UnaryExec):
    def __init__(self, condition: Expression, child: Exec):
        super().__init__(child)
        self.condition = condition

    def execute_partition(self, pidx):
        import pyarrow as pa
        import pyarrow.compute as pc
        from spark_rapids_tpu.expressions.evaluator import (host_batch_tcols,
                                                            tcol_to_host_column)
        from spark_rapids_tpu.expressions.base import EvalContext
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                cols = host_batch_tcols(b)
                ctx = EvalContext(cols, "cpu", b.row_count)
                pred = self.condition.eval_cpu(ctx)
                keep_col = tcol_to_host_column(pred, b.row_count)
                mask = pc.fill_null(keep_col.arrow.cast(pa.bool_()), False)
                rb = b.to_arrow().filter(mask)
                yield batch_from_arrow(pa.Table.from_batches([rb]))

    def node_desc(self):
        return f"Filter[{self.condition.sql()}]"


class TpuFilterExec(UnaryExec):
    """Filter = fused predicate eval + stable compaction gather; bucket is
    preserved so no recompilation across batches (see ops.batch_ops)."""

    is_device = True

    def __init__(self, condition: Expression, child: Exec):
        super().__init__(child)
        self.condition = condition

    def execute_partition(self, pidx):
        from spark_rapids_tpu.expressions.base import EvalContext, valid_array
        from spark_rapids_tpu.expressions.evaluator import device_batch_tcols
        from spark_rapids_tpu.ops import compact_batch
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                cols = device_batch_tcols(b)
                ctx = EvalContext(cols, "tpu", b.bucket)
                pred = self.condition.eval_tpu(ctx)
                keep = valid_array(pred, ctx)
                if not pred.is_scalar:
                    keep = keep & pred.data
                else:
                    keep = keep & bool(pred.data)
                # padding rows must never be kept
                rowpos = jnp.arange(b.bucket)
                keep = keep & (rowpos < b.row_count)
                yield compact_batch(b, keep)

    def node_desc(self):
        return f"TpuFilter[{self.condition.sql()}]"


# ---------------------------------------------------------------------------
# Range
# ---------------------------------------------------------------------------

class CpuRangeExec(LeafExec):
    """SELECT id FROM range(start, end, step) (reference GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, batch_rows: int = 1 << 20):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self._parts = max(1, num_partitions)
        self.batch_rows = batch_rows

    @property
    def schema(self):
        return T.StructType([T.StructField("id", T.LONG, False)])

    @property
    def num_partitions(self):
        return self._parts

    def _partition_range(self, pidx):
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self._parts)
        lo = min(pidx * per, total)
        hi = min(lo + per, total)
        return lo, hi

    def execute_partition(self, pidx):
        from spark_rapids_tpu.columnar.batch import batch_from_pydict
        lo, hi = self._partition_range(pidx)
        pos = lo
        while pos < hi:
            n = min(self.batch_rows, hi - pos)
            vals = self.start + (pos + np.arange(n, dtype=np.int64)) * self.step
            yield batch_from_pydict({"id": vals}, self.schema)
            pos += n

    def node_desc(self):
        return f"Range({self.start}, {self.end}, {self.step})"


class TpuRangeExec(CpuRangeExec):
    is_device = True

    def __init__(self, cpu: CpuRangeExec):
        super().__init__(cpu.start, cpu.end, cpu.step, cpu._parts,
                         cpu.batch_rows)

    def execute_partition(self, pidx):
        from spark_rapids_tpu.columnar.column import (DeviceColumn, _jnp,
                                                      bucket_rows)
        jnp = _jnp()
        lo, hi = self._partition_range(pidx)
        pos = lo
        while pos < hi:
            n = min(self.batch_rows, hi - pos)
            b = bucket_rows(n)
            vals = self.start + (pos + jnp.arange(b, dtype=np.int64)) * self.step
            valid = jnp.arange(b) < n
            col = DeviceColumn(vals, valid, n, T.LONG)
            yield ColumnarBatch([col], n, ["id"])
            pos += n

    def node_desc(self):
        return f"TpuRange({self.start}, {self.end}, {self.step})"


# ---------------------------------------------------------------------------
# Limit / Union / Sample
# ---------------------------------------------------------------------------

class CpuLimitExec(UnaryExec):
    """Local limit per partition; with single-partition input it is global
    (reference: Local/Global/CollectLimitExec trio)."""

    def __init__(self, n: int, child: Exec):
        super().__init__(child)
        self.n = n

    def execute_partition(self, pidx):
        from spark_rapids_tpu.plan.base import closing_source
        left = self.n
        # budget check BEFORE pulling: a satisfied limit must not make
        # the source decode one more batch just to discard it, and the
        # deterministic close propagates the early exit upstream (stops
        # prefetch producers, releases queued spillables)
        with closing_source(self.child.execute_partition(pidx)) as it:
            while left > 0:
                try:
                    b = next(it)
                except StopIteration:
                    return
                if b.row_count <= left:
                    left -= b.row_count
                    yield b
                else:
                    yield b.slice(0, left)
                    left = 0

    def node_desc(self):
        return f"Limit[{self.n}]"


#: default for spark.rapids.sql.limit.deferredForceInterval — the limit
#: execs carry their convert-time conf value per instance
LIMIT_DEFERRED_FORCE_INTERVAL = 8


def _deferred_limited(batches, n: int, force_interval=None):
    """Limit over a batch stream with the remaining budget kept ON DEVICE
    while counts are deferred (forcing each batch's count would cost a
    device sync per batch).  Amortized early exit: every
    ``force_interval``-th (default LIMIT_DEFERRED_FORCE_INTERVAL)
    deferred batch forces the budget once so a satisfied limit stops
    pulling the source."""
    import numpy as _np

    if force_interval is None:      # explicit sentinel: a conf value of
        force_interval = LIMIT_DEFERRED_FORCE_INTERVAL   # 1 must stick

    from spark_rapids_tpu.columnar.column import (DeferredCount, _jnp,
                                                  rc_traceable)
    from spark_rapids_tpu.ops import take_front
    from spark_rapids_tpu.plan.base import closing_source
    jnp = _jnp()
    left = n   # int until a deferred count is consumed
    deferred_batches = 0
    # the satisfied-limit return (and a downstream close) must stop the
    # source deterministically, not at GC time
    with closing_source(iter(batches)) as it:
        while True:
            # budget check BEFORE pulling: a satisfied limit must not
            # start the next partition's pipeline just to discard its
            # first batch
            if isinstance(left, int) and left <= 0:
                return
            try:
                b = next(it)
            except StopIteration:
                return
            rc = b.row_count
            if isinstance(left, int) and \
                    not (isinstance(rc, DeferredCount) and not rc.is_forced):
                if int(rc) <= left:
                    left -= int(rc)
                    yield b
                else:
                    yield take_front(b, left)
                    left = 0
                continue
            out = take_front(b, left if isinstance(left, int)
                             else DeferredCount(left))
            left = jnp.maximum(
                jnp.asarray(rc_traceable(left)) -
                jnp.asarray(rc_traceable(out.row_count)), 0)
            yield out
            deferred_batches += 1
            if deferred_batches % force_interval == 0:
                from spark_rapids_tpu.aux import transitions as TR
                left = int(TR.fetch(left, site="limit-force"))


class TpuLimitExec(UnaryExec):
    is_device = True

    #: conf-at-convert-time (spark.rapids.sql.limit.deferredForceInterval)
    deferred_force_interval = None

    def __init__(self, n: int, child: Exec):
        super().__init__(child)
        self.n = n

    def execute_partition(self, pidx):
        yield from _deferred_limited(self.child.execute_partition(pidx),
                                     self.n,
                                     self.deferred_force_interval)

    def node_desc(self):
        return f"TpuLimit[{self.n}]"


class CpuCteCacheExec(UnaryExec):
    """Materializes a multiply-referenced CTE subtree ONCE and replays the
    batches to every reference (Spark analog: WithCTE + ReusedExchangeExec
    collapse repeated CTE branches; the reference relies on Spark for this
    and only sees the deduped plan).  The analyzer wraps a CTE plan in
    this node when the statement references it more than once; conversion
    copies are re-merged by the exchange-reuse pass keyed on ``origin``
    (plan/overrides.py reuse_exchanges)."""

    #: execution epoch the next execution must rebuild for (stamped by
    #: ``refresh_cte_epochs`` per prepared action); class-level 0 keeps
    #: directly-driven test execs caching across calls
    _expected_epoch = 0

    def __init__(self, child: Exec):
        super().__init__(child)
        self._cache = None
        #: epoch the cached batches were materialized under — a cache
        #: from a previous action / speculation replay / changed input
        #: file set must never replay (it is only valid within the ONE
        #: action whose epoch stamped it)
        self._cache_epoch = None
        #: identity of the logical (analyzer-built) node — survives the
        #: shallow copies the rewrite passes make, letting reuse collapse
        #: converted copies back into one caching instance
        self.origin = id(self)

    def execute_partition(self, pidx):
        from spark_rapids_tpu.plan.base import release_semaphore_for_wait
        if self._cache is None or self._cache_epoch != self._expected_epoch:
            release_semaphore_for_wait()
            with self._exec_lock:
                if self._cache is None or \
                        self._cache_epoch != self._expected_epoch:
                    self._cache = [list(self.child.execute_partition(p))
                                   for p in range(self.child.num_partitions)]
                    self._cache_epoch = self._expected_epoch
        yield from self._cache[pidx]

    def node_desc(self):
        return "CteCache"


class TpuCteCacheExec(CpuCteCacheExec):
    is_device = True

    def __init__(self, child: Exec, origin: int):
        super().__init__(child)
        self.origin = origin

    def node_desc(self):
        return "TpuCteCache"


def refresh_cte_epochs(plan: Exec) -> None:
    """Arms every CTE cache in ``plan`` for ONE upcoming execution: a
    fresh process-wide epoch is stamped on each node, so every reference
    within the action shares the single materialization while batches
    cached by a PREVIOUS action (a speculation replay in exact mode, a
    re-executed plan-cache entry, inputs whose files changed) always
    rebuild instead of replaying stale."""
    from spark_rapids_tpu.plan.base import next_execution_epoch
    nodes = [n for n in plan.collect_nodes()
             if isinstance(n, CpuCteCacheExec)]
    if not nodes:
        return
    epoch = next_execution_epoch()
    for n in nodes:
        n._expected_epoch = epoch


class CpuGlobalLimitExec(UnaryExec):
    """Single-output-partition global limit: streams child partitions in
    order until n rows are emitted (reference: CollectLimit/GlobalLimit
    trio, limit.scala; in-process, the 'shuffle to one partition' collapses
    to sequentially draining child partitions)."""

    def __init__(self, n: int, child: Exec):
        super().__init__(child)
        self.n = n

    @property
    def num_partitions(self):
        return 1

    def _limited(self, slicer):
        from spark_rapids_tpu.plan.base import closing_source
        left = self.n
        for cp in range(self.child.num_partitions):
            if left <= 0:
                return
            # check before every pull so a budget exhausted mid-partition
            # never decodes the discarded next batch
            with closing_source(self.child.execute_partition(cp)) as it:
                while left > 0:
                    try:
                        b = next(it)
                    except StopIteration:
                        break
                    if b.row_count <= left:
                        left -= b.row_count
                        yield b
                    else:
                        yield slicer(b, left)
                        left = 0

    def execute_partition(self, pidx):
        yield from self._limited(lambda b, k: b.slice(0, k))

    def node_desc(self):
        return f"GlobalLimit[{self.n}]"


class TpuGlobalLimitExec(CpuGlobalLimitExec):
    is_device = True

    #: conf-at-convert-time (spark.rapids.sql.limit.deferredForceInterval)
    deferred_force_interval = None

    def execute_partition(self, pidx):
        def stream():
            for cp in range(self.child.num_partitions):
                yield from self.child.execute_partition(cp)
        yield from _deferred_limited(stream(), self.n,
                                     self.deferred_force_interval)

    def node_desc(self):
        return f"TpuGlobalLimit[{self.n}]"


class CpuCoalescePartitionsExec(UnaryExec):
    """Shuffle-free partition-count reduction: merges adjacent child
    partitions (Spark coalesce() contract — never increases count, keeps
    per-partition order, no data movement)."""

    def __init__(self, n: int, child: Exec):
        super().__init__(child)
        self.n = n

    @property
    def num_partitions(self):
        return max(1, min(self.n, self.child.num_partitions))

    def execute_partition(self, pidx):
        total = self.child.num_partitions
        outs = self.num_partitions
        per = -(-total // outs)
        for cp in range(pidx * per, min((pidx + 1) * per, total)):
            yield from self.child.execute_partition(cp)

    def node_desc(self):
        return f"CoalescePartitions[{self.num_partitions}]"


class TpuCoalescePartitionsExec(CpuCoalescePartitionsExec):
    is_device = True

    def node_desc(self):
        return f"TpuCoalescePartitions[{self.num_partitions}]"


class CpuUnionExec(Exec):
    def __init__(self, children: Sequence[Exec]):
        super().__init__(children)

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def _locate(self, pidx):
        for c in self.children:
            if pidx < c.num_partitions:
                return c, pidx
            pidx -= c.num_partitions
        raise IndexError(pidx)

    def execute_partition(self, pidx):
        child, sub = self._locate(pidx)
        yield from child.execute_partition(sub)

    def node_desc(self):
        return f"Union[{len(self.children)}]"


class TpuUnionExec(CpuUnionExec):
    is_device = True

    def node_desc(self):
        return f"TpuUnion[{len(self.children)}]"


class CpuSampleExec(UnaryExec):
    """Bernoulli sample (reference GpuSampleExec)."""

    def __init__(self, fraction: float, seed: int, child: Exec):
        super().__init__(child)
        self.fraction = fraction
        self.seed = seed

    def execute_partition(self, pidx):
        import pyarrow as pa
        rng = np.random.default_rng(self.seed + pidx)
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                mask = rng.random(b.row_count) < self.fraction
                rb = b.to_arrow().filter(pa.array(mask))
                yield batch_from_arrow(pa.Table.from_batches([rb]))

    def node_desc(self):
        return f"Sample[{self.fraction}]"


class TpuSampleExec(UnaryExec):
    is_device = True

    def __init__(self, fraction: float, seed: int, child: Exec):
        super().__init__(child)
        self.fraction = fraction
        self.seed = seed

    def execute_partition(self, pidx):
        import jax
        from spark_rapids_tpu.ops import compact_batch
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        key = jax.random.PRNGKey(self.seed + pidx)
        with closing_source(self.child.execute_partition(pidx)) as it:
            for i, b in enumerate(it):
                key, sub = jax.random.split(key)
                u = jax.random.uniform(sub, (b.bucket,))
                keep = (u < self.fraction) & \
                    (jnp.arange(b.bucket) < b.row_count)
                yield compact_batch(b, keep)

    def node_desc(self):
        return f"TpuSample[{self.fraction}]"


# ---------------------------------------------------------------------------
# Transitions (reference: GpuRowToColumnarExec / GpuColumnarToRowExec /
# HostColumnarToGpu; ours collapse to host<->device batch copies)
# ---------------------------------------------------------------------------

class TpuFilterProjectExec(UnaryExec):
    """Whole-stage fusion of Filter -> Project: predicate eval, projection,
    and stable compaction run as ONE jitted XLA program per batch — no
    intermediate columns materialize in HBM and dispatch overhead halves
    (the structural advantage over the reference's one-kernel-per-operator
    cuDF dispatch; planner pass fuse_device_stages builds these)."""

    is_device = True

    def __init__(self, condition: Expression, exprs: Sequence[Expression],
                 child: Exec):
        super().__init__(child)
        self.condition = condition
        self.exprs = list(exprs)

    @property
    def schema(self):
        return _project_schema(self.exprs)

    def execute_partition(self, pidx):
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import (DeferredCount,
                                                      DeviceColumn, _jnp)
        from spark_rapids_tpu.expressions.base import (EvalContext,
                                                       valid_array)
        from spark_rapids_tpu.expressions.evaluator import (
            _signature, device_batch_tcols, tcol_to_device_column)
        jnp = _jnp()
        from spark_rapids_tpu.columnar.encoding import materialize_batch
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                # this pre-fusion node reads raw column planes; the fused
                # stage exec (plan/stages.py) is the encoding-aware path
                b = materialize_batch(b, site="operator")
                key = (_signature([self.condition] + self.exprs, b), b.bucket)

                def build(dtypes=tuple(c.data_type for c in b.columns),
                          bucket=b.bucket):
                    # captures frozen at build time (NOT loop cells): a later
                    # jax retrace of this cached program must see the bucket/
                    # dtypes it was keyed under, not the loop's current batch
                    cond, exprs = self.condition, self.exprs

                    def run(arrs, row_count):
                        cols = [TCol(d, v, dt, lengths=ln, elem_valid=ev)
                                for (d, v, ln, ev), dt in zip(arrs, dtypes)]
                        ctx = EvalContext(cols, "tpu", bucket)
                        pred = cond.eval_tpu(ctx)
                        keep = valid_array(pred, ctx)
                        if not pred.is_scalar:
                            keep = keep & pred.data
                        else:
                            keep = keep & bool(pred.data)
                        keep = keep & (jnp.arange(bucket) < row_count)
                        from spark_rapids_tpu.ops.batch_ops import \
                            prefix_sum
                        dest = prefix_sum(keep, jnp) - 1
                        dest = jnp.where(keep, dest, bucket)
                        cnt = jnp.sum(keep)
                        live = jnp.arange(bucket) < cnt
                        outs = []
                        for e in exprs:
                            dc = tcol_to_device_column(e.eval_tpu(ctx), 0,
                                                       bucket, jnp)
                            nd = jnp.zeros_like(dc.data).at[dest].set(
                                dc.data, mode="drop")
                            nv = jnp.zeros_like(dc.validity).at[dest].set(
                                dc.validity & keep, mode="drop") & live
                            nl = None if dc.lengths is None else \
                                jnp.zeros_like(dc.lengths).at[dest].set(
                                    dc.lengths, mode="drop")
                            ne = None if dc.elem_valid is None else \
                                jnp.zeros_like(dc.elem_valid).at[dest].set(
                                    dc.elem_valid, mode="drop")
                            outs.append((nd, nv, nl, ne))
                        return outs, cnt

                    return run
                from spark_rapids_tpu.exec.stage_compiler import get_or_build
                fn = get_or_build("basic.filter_project", key, build)
                arrs = [(c.data, c.validity, c.lengths, c.elem_valid)
                        for c in b.columns]
                from spark_rapids_tpu.columnar.column import rc_traceable
                outs, cnt = fn(arrs, rc_traceable(b.row_count))
                rc = DeferredCount(cnt)
                cols = [DeviceColumn(d, v, rc, e.data_type, ln, ev)
                        for (d, v, ln, ev), e in zip(outs, self.exprs)]
                from spark_rapids_tpu.expressions.evaluator import _out_names
                yield ColumnarBatch(cols, rc, _out_names(self.exprs))

    def node_desc(self):
        return (f"TpuFilterProject[{self.condition.sql()}; "
                f"{', '.join(e.sql() for e in self.exprs)}]")


class TpuMaterializeEncodedExec(UnaryExec):
    """Explicit eager-decode boundary: every encoded column of every
    child batch materializes here.  The plan/encoding.py planner pass
    inserts this directly above encoded-capable device scans when
    ``spark.rapids.sql.encoding.lateMaterialization`` is off — the scan
    still uploads only the codes (the H2D win), but operators only
    ever see plain columns."""

    is_device = True

    def execute_partition(self, pidx):
        from spark_rapids_tpu.columnar.encoding import materialize_batch
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                yield materialize_batch(b, site="eager")

    def node_desc(self):
        return "TpuMaterializeEncoded"


class HostToDeviceExec(UnaryExec):
    is_device = True

    def execute_partition(self, pidx):
        yield from upload_batches(self.child.execute_partition(pidx))

    def node_desc(self):
        return "HostToDevice"


class DeviceToHostExec(UnaryExec):
    """Device->host copy; the semaphore stays held until task completion
    (run_task), matching the reference's completion-listener release."""

    is_device = False

    #: conf-at-plan-time speculative download row cap
    #: (spark.rapids.sql.collect.speculativeRows); ``None`` falls back
    #: to the transfer-module default.  Set by ``insert_transitions``
    #: so per-query conf rides the plan instance
    dl_spec_rows = None

    def execute_partition(self, pidx):
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                yield b.to_host(spec_rows=self.dl_spec_rows)

    def node_desc(self):
        return "DeviceToHost"


class TpuCoalesceBatchesExec(UnaryExec):
    """Concatenates small device batches up to a target size (reference:
    GpuCoalesceBatches.scala CoalesceGoal/TargetSize)."""

    is_device = True

    def __init__(self, child: Exec, target_bytes: int = 512 << 20,
                 require_single_batch: bool = False):
        super().__init__(child)
        self.target_bytes = target_bytes
        self.require_single_batch = require_single_batch

    def execute_partition(self, pidx):
        from spark_rapids_tpu.ops import concat_batches
        pending: List[ColumnarBatch] = []
        pending_bytes = 0
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                pending.append(b)
                pending_bytes += b.sized_nbytes()
                if not self.require_single_batch and \
                        pending_bytes >= self.target_bytes:
                    yield concat_batches(pending)
                    pending, pending_bytes = [], 0
        if pending:
            yield concat_batches(pending)

    def node_desc(self):
        goal = "RequireSingleBatch" if self.require_single_batch else \
            f"TargetSize({self.target_bytes})"
        return f"TpuCoalesceBatches[{goal}]"
