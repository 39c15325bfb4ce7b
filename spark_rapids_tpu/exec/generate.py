"""Generate operator: explode / posexplode (+ _outer variants).

Reference: GpuGenerateExec.scala — GpuExplode/GpuPosExplode lower to cuDF
explode/explode_position (+outer).  TPU design: the array column already
lives as a padded rectangular plane, so explode is ONE device gather — the
output row for flat position p maps to (row = searchsorted(cum_lens, p),
within = p - cum_start(row)); repeated other-columns ride the same gather.
One host sync fetches the output row count (to size the output bucket),
matching the one-sync-per-batch discipline of filter/compact.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.expressions.base import BoundReference, Expression
from spark_rapids_tpu.plan.base import Exec, UnaryExec, closing_source


class CpuGenerateExec(UnaryExec):
    """explode(array_col): one output row per element; other columns are
    repeated.  ``outer`` keeps null/empty-array rows with a null element;
    ``position`` adds the element ordinal column (posexplode)."""

    def __init__(self, generator: Expression, child: Exec,
                 outer: bool = False, position: bool = False,
                 element_name: str = "col", pos_name: str = "pos"):
        super().__init__(child)
        dt = generator.data_type
        if not isinstance(dt, T.ArrayType):
            raise TypeError(f"explode needs an array input, got "
                            f"{dt.simple_name}")
        self.generator = generator
        self.outer = outer
        self.position = position
        self.element_name = element_name
        self.pos_name = pos_name

    @property
    def schema(self):
        fields = list(self.child.schema.fields)
        if self.position:
            fields.append(T.StructField(self.pos_name, T.INT, self.outer))
        fields.append(T.StructField(
            self.element_name, self.generator.data_type.element_type, True))
        return T.StructType(fields)

    def execute_partition(self, pidx):
        import pyarrow as pa
        from spark_rapids_tpu.columnar.batch import batch_from_arrow
        from spark_rapids_tpu.expressions.base import EvalContext, valid_array
        from spark_rapids_tpu.expressions.evaluator import host_batch_tcols
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                cols = host_batch_tcols(b)
                ctx = EvalContext(cols, "cpu", b.row_count)
                arr = self.generator.eval_cpu(ctx)
                valid = valid_array(arr, ctx)
                src_rows: List[int] = []
                poss: List[Optional[int]] = []
                elems: List = []
                for i in range(b.row_count):
                    lst = arr.data[i] if valid[i] else None
                    if lst:
                        for j, e in enumerate(lst):
                            src_rows.append(i)
                            poss.append(j)
                            elems.append(e)
                    elif self.outer:
                        src_rows.append(i)
                        poss.append(None)
                        elems.append(None)
                tab = pa.Table.from_batches([b.to_arrow()])
                taken = tab.take(pa.array(src_rows, type=pa.int64()))
                out_cols = [c.combine_chunks() if isinstance(c, pa.ChunkedArray)
                            else c for c in taken.columns]
                names = list(tab.schema.names)
                if self.position:
                    out_cols.append(pa.array(poss, type=pa.int32()))
                    names.append(self.pos_name)
                out_cols.append(pa.array(
                    elems, type=T.to_arrow(self.generator.data_type.element_type)))
                names.append(self.element_name)
                # from_arrays keeps duplicate names (the explode alias may
                # collide with a child column; a dict would silently drop one)
                yield batch_from_arrow(pa.Table.from_arrays(out_cols,
                                                            names=names))

    def node_desc(self):
        kind = "PosExplode" if self.position else "Explode"
        return f"Generate[{kind}{'Outer' if self.outer else ''}" \
               f"({self.generator.sql()})]"


class TpuGenerateExec(CpuGenerateExec):
    is_device = True

    def __init__(self, cpu: CpuGenerateExec):
        super().__init__(cpu.generator, cpu.children[0], cpu.outer,
                         cpu.position, cpu.element_name, cpu.pos_name)

    def execute_partition(self, pidx):
        import jax
        from spark_rapids_tpu.columnar.column import (DeviceColumn,
                                                      bucket_rows, _jnp)
        from spark_rapids_tpu.expressions.base import EvalContext, valid_array
        from spark_rapids_tpu.expressions.evaluator import device_batch_tcols
        from spark_rapids_tpu.ops.batch_ops import gather_batch
        jnp = _jnp()
        elem_dt = self.generator.data_type.element_type
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                cols = device_batch_tcols(b)
                ctx = EvalContext(cols, "tpu", b.bucket)
                arr = self.generator.eval_tpu(ctx)
                valid = valid_array(arr, ctx)
                rowpos = jnp.arange(b.bucket)
                live_row = valid & (rowpos < b.row_count)
                lens = jnp.where(live_row, arr.lengths, 0).astype(np.int64)
                if self.outer:
                    in_row = rowpos < b.row_count
                    fan = jnp.where(in_row & (lens == 0), 1, lens)
                else:
                    fan = lens
                from spark_rapids_tpu.ops.batch_ops import prefix_sum
                cum = prefix_sum(fan, jnp)
                total = int(cum[-1])           # ONE sync: output size
                if total == 0:
                    continue
                out_bucket = bucket_rows(total)
                outpos = jnp.arange(out_bucket, dtype=np.int64)
                src = jnp.searchsorted(cum, outpos, side="right")
                src = jnp.clip(src, 0, b.bucket - 1)
                start = cum[src] - fan[src]
                within = outpos - start
                out_live = outpos < total
                # element plane gather
                w = arr.data.shape[1]
                safe_within = jnp.clip(within, 0, w - 1).astype(np.int64)
                elem = arr.data[src, safe_within]
                elem_ok = arr.elem_valid[src, safe_within] & \
                    (within < lens[src]) & out_live
                repeated = gather_batch(b, src, total, idx_valid=out_live)
                out_cols = list(repeated.columns)
                names = list(repeated.names)
                if self.position:
                    # outer-null fan rows have within==0 >= lens==0 -> null pos
                    pos_ok = out_live & (within < lens[src])
                    out_cols.append(DeviceColumn(
                        within.astype(np.int32), pos_ok, total, T.INT))
                    names.append(self.pos_name)
                out_cols.append(DeviceColumn(elem, elem_ok, total, elem_dt))
                names.append(self.element_name)
                yield ColumnarBatch(out_cols, total, names)

    def node_desc(self):
        return "Tpu" + super().node_desc()


# plan-rewrite registration (reference: GpuOverrides GenerateExec rule)
from spark_rapids_tpu.plan import typechecks as TS  # noqa: E402
from spark_rapids_tpu.plan.overrides import register_exec  # noqa: E402

register_exec(CpuGenerateExec,
              convert=lambda p, m: TpuGenerateExec(p),
              sig=TS.BASIC_WITH_ARRAYS,
              exprs_of=lambda p: [p.generator],
              desc="explode/posexplode via one device gather")
