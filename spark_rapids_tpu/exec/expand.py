"""Expand and TakeOrderedAndProject operators.

Reference: GpuExpandExec.scala (Expand's projection-list fan-out that powers
ROLLUP/CUBE/GROUPING SETS) and the TakeOrderedAndProject registration in
GpuOverrides.scala commonExecs (:3999-4311) — per-partition top-K, gather to
one partition, final top-K, then project.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux.tracing import add_count
from spark_rapids_tpu.columnar.batch import concat_host_batches
from spark_rapids_tpu.exec.sort import (SortSpec, device_sort_batch,
                                        host_sort_batch)
from spark_rapids_tpu.expressions.base import Expression
from spark_rapids_tpu.expressions.evaluator import (eval_exprs_cpu,
                                                    eval_exprs_tpu)
from spark_rapids_tpu.plan.base import Exec, UnaryExec, closing_source


class CpuExpandExec(UnaryExec):
    """Emits one output row-set per projection list for every input batch
    (Spark ExpandExec; each projection is the same arity and output names).
    """

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: Exec):
        super().__init__(child)
        if not projections:
            raise ValueError("Expand needs at least one projection")
        arity = len(projections[0])
        for p in projections:
            if len(p) != arity:
                raise ValueError("Expand projections must share arity")
        if len(names) != arity:
            raise ValueError("Expand names must match projection arity")
        self.projections = [list(p) for p in projections]
        self.names = list(names)

    @property
    def schema(self):
        fields = []
        for j, name in enumerate(self.names):
            dt = self.projections[0][j].data_type
            nullable = any(p[j].nullable for p in self.projections)
            for p in self.projections[1:]:
                dt = T.common_type(dt, p[j].data_type)
            fields.append(T.StructField(name, dt, nullable))
        return T.StructType(fields)

    def _coerced(self, proj):
        """Casts each projection output to the common column type so every
        emitted batch has the unified Expand schema."""
        from spark_rapids_tpu.expressions.cast import Cast
        from spark_rapids_tpu.expressions.base import Alias
        out_schema = self.schema
        coerced = []
        for j, e in enumerate(proj):
            want = out_schema.fields[j].data_type
            if e.data_type != want:
                e = Cast(e, want)
            coerced.append(Alias(e, self.names[j]))
        return coerced

    def execute_partition(self, pidx):
        coerced = [self._coerced(p) for p in self.projections]
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                for proj in coerced:
                    yield eval_exprs_cpu(proj, b, self.names)

    def node_desc(self):
        return f"Expand[{len(self.projections)} projections]"


class TpuExpandExec(CpuExpandExec):
    """Device Expand: each projection list is one fused XLA program
    (``expand.project``) over the same resident input batch — the fan-out
    costs no extra host transfers.

    The fan-out multiplies what it is handed, padding included, and the
    aggregation above it pays for every padded row again: it counts what
    it hands on (``expand_rows_padded``) and cuts nothing itself.  A join
    chain over a large probe side hands on the bucket of what it holds
    (``exec/joins.py``; the floor is ``columnar/column.py``'s
    ``SIZED_MIN_BUCKET``)."""

    is_device = True

    def __init__(self, cpu: CpuExpandExec):
        super().__init__(cpu.projections, cpu.names, cpu.children[0])

    def execute_partition(self, pidx):
        coerced = [self._coerced(p) for p in self.projections]
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                for proj in coerced:
                    add_count("expand_rows_padded", b.bucket)
                    yield eval_exprs_tpu(proj, b, self.names,
                                         kind="expand.project")

    def node_desc(self):
        return f"TpuExpand[{len(self.projections)} projections]"


class CpuTakeOrderedAndProjectExec(UnaryExec):
    """ORDER BY + LIMIT [+ projection] collapsed into one operator.

    Local top-K per child partition, then a final merge + top-K + project in
    the single output partition (reference: GpuTopN in limit.scala driven by
    the TakeOrderedAndProjectExec rule)."""

    def __init__(self, n: int, specs: Sequence[SortSpec], child: Exec,
                 project: Optional[Sequence[Expression]] = None):
        super().__init__(child)
        self.n = n
        self.specs = list(specs)
        self.project = list(project) if project else None

    @property
    def schema(self):
        if self.project is None:
            return self.child.schema
        from spark_rapids_tpu.expressions.evaluator import _out_names
        return T.StructType([
            T.StructField(nm, e.data_type, e.nullable)
            for nm, e in zip(_out_names(self.project), self.project)])

    @property
    def num_partitions(self):
        return 1

    def _local_topk(self, cp: int):
        batches = list(self.child.execute_partition(cp))
        if not batches:
            return None
        b = host_sort_batch(concat_host_batches(batches), self.specs)
        return b.slice(0, min(self.n, b.row_count))

    def execute_partition(self, pidx):
        tops = [t for cp in range(self.child.num_partitions)
                for t in [self._local_topk(cp)] if t is not None]
        if not tops:
            return
        merged = host_sort_batch(concat_host_batches(tops), self.specs)
        merged = merged.slice(0, min(self.n, merged.row_count))
        if self.project is not None:
            merged = eval_exprs_cpu(self.project, merged)
        yield merged

    def node_desc(self):
        ks = ", ".join(f"{s.expr.sql()} {'ASC' if s.ascending else 'DESC'}"
                       for s in self.specs)
        return f"TakeOrderedAndProject[n={self.n}, {ks}]"


class TpuTakeOrderedAndProjectExec(CpuTakeOrderedAndProjectExec):
    is_device = True

    def __init__(self, cpu: CpuTakeOrderedAndProjectExec):
        super().__init__(cpu.n, cpu.specs, cpu.children[0], cpu.project)

    def _local_topk(self, cp: int):
        from spark_rapids_tpu.ops import concat_batches, take_front
        batches = list(self.child.execute_partition(cp))
        if not batches:
            return None
        b = device_sort_batch(concat_batches(batches), self.specs)
        return take_front(b, self.n)   # take_front clamps without a sync

    def execute_partition(self, pidx):
        from spark_rapids_tpu.ops import concat_batches, take_front
        tops = [t for cp in range(self.child.num_partitions)
                for t in [self._local_topk(cp)] if t is not None]
        if not tops:
            return
        merged = device_sort_batch(concat_batches(tops), self.specs)
        merged = take_front(merged, self.n)
        if self.project is not None:
            merged = eval_exprs_tpu(self.project, merged)
        yield merged

    def node_desc(self):
        return "Tpu" + super().node_desc()


# plan-rewrite registrations
from spark_rapids_tpu.plan import typechecks as TS  # noqa: E402
from spark_rapids_tpu.plan.overrides import register_exec  # noqa: E402

register_exec(CpuExpandExec,
              convert=lambda p, m: TpuExpandExec(p),
              sig=TS.BASIC_WITH_ARRAYS,
              exprs_of=lambda p: [e for proj in p.projections for e in proj],
              desc="projection fan-out (ROLLUP/CUBE/GROUPING SETS)")
register_exec(CpuTakeOrderedAndProjectExec,
              convert=lambda p, m: TpuTakeOrderedAndProjectExec(p),
              sig=TS.BASIC_WITH_ARRAYS,
              exprs_of=lambda p: ([s.expr for s in p.specs]
                                  + (p.project or [])),
              extra_tag=lambda m: TS.no_array_keys(
                  [s.expr for s in m.plan.specs], m, "sort key"),
              desc="order-by + limit + project in one pass")
