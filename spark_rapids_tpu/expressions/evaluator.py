"""Batch-level expression evaluation: whole-stage XLA fusion.

The TPU path stages the ENTIRE projection/filter expression list into one
traced function and jits it per (expression-list, input schema, bucket) — so
XLA fuses every elementwise op, cast, and hash into a single kernel.  This is
the structural performance advantage over the reference, which dispatches one
cuDF kernel per operator node (GpuProjectExec.project -> columnarEval chain).

The CPU path evaluates the same trees with the numpy backend (fallback +
differential oracle).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, HostColumn
from spark_rapids_tpu.expressions.base import (EvalContext, Expression, TCol,
                                               expr_key, valid_array)


# ---------------------------------------------------------------------------
# batch <-> TCol bridges
# ---------------------------------------------------------------------------

def device_batch_tcols(batch: ColumnarBatch) -> List[TCol]:
    """Bridges a device batch into evaluation TCols.  Encoded columns
    (dictionary codes / RLE runs) materialize here — the transparent
    per-column fallback for every operator that is not encoding-aware
    (the fused-stage path consumes codes directly and never calls
    this on encoded columns it keeps)."""
    from spark_rapids_tpu.columnar.encoding import materialize_batch
    batch = materialize_batch(batch, site="operator")
    return [TCol(c.data, c.validity, c.data_type, lengths=c.lengths,
                 elem_valid=c.elem_valid)
            for c in batch.columns]


def host_batch_tcols(batch: HostColumnarBatch) -> List[TCol]:
    out = []
    for c in batch.columns:
        dt = c.data_type
        valid = c.validity_np()
        if isinstance(dt, (T.StringType, T.BinaryType)) or dt.is_nested:
            # CPU backend: object array of python values (str / list / dict)
            data = np.empty(len(c), dtype=object)
            lst = c.to_pylist()
            for i, v in enumerate(lst):
                data[i] = v
            out.append(TCol(data, valid, dt))
        elif isinstance(dt, T.DecimalType) and dt.is_decimal128:
            # CPU backend: python-int object array of unscaled values
            raw = c.data_np()
            data = np.empty(len(c), dtype=object)
            for i in range(len(c)):
                data[i] = (int(raw[i, 0]) << 64) | (int(raw[i, 1])
                                                    & 0xFFFFFFFFFFFFFFFF)
            out.append(TCol(data, valid, dt))
        else:
            out.append(TCol(c.data_np(), valid, dt))
    return out


def tcol_to_device_column(tc: TCol, row_count: int, bucket: int,
                          xp) -> DeviceColumn:
    data, valid, lens = tc.data, tc.valid, tc.lengths
    if not tc.is_scalar and isinstance(tc.dtype, T.ArrayType):
        return DeviceColumn(data, valid, row_count, tc.dtype, lengths=lens,
                            elem_valid=tc.elem_valid)
    if tc.is_scalar:
        # densify a scalar result
        ctx = EvalContext([], "tpu", bucket)
        from spark_rapids_tpu.expressions.base import materialize
        if isinstance(tc.dtype, (T.StringType, T.BinaryType)):
            from spark_rapids_tpu.expressions.predicates import _densify_string
            d = _densify_string(tc, ctx, xp)
            data, valid, lens = d.data, valid_array(tc, ctx), d.lengths
        else:
            data = materialize(tc, ctx, tc.dtype.np_dtype)
            valid = valid_array(tc, ctx)
    return DeviceColumn(data, valid, row_count, tc.dtype, lengths=lens)


def tcol_to_host_column(tc: TCol, row_count: int) -> HostColumn:
    import pyarrow as pa
    dt = tc.dtype
    if tc.is_scalar:
        v = tc.data if tc.valid else None
        if isinstance(dt, T.DecimalType):
            import decimal
            vals = [None if v is None else decimal.Decimal(v)] * row_count
            return HostColumn(pa.array(vals, type=T.to_arrow(dt)), dt)
        return HostColumn(pa.array([_pyify(v, dt)] * row_count,
                                   type=T.to_arrow(dt)), dt)
    valid = np.asarray(tc.valid)
    if valid.ndim == 0:
        # all-literal expression trees keep scalar (0-d) planes through
        # binary kernels; broadcast to the logical row count
        valid = np.full(row_count, bool(valid))
    if not (isinstance(dt, (T.StringType, T.BinaryType)) or dt.is_nested):
        d = np.asarray(tc.data)
        if d.ndim == 0:
            tc = TCol(np.full(row_count, d[()]), valid, dt)
    if isinstance(dt, (T.StringType, T.BinaryType)) or dt.is_nested:
        vals = [tc.data[i] if valid[i] else None for i in range(row_count)]
        return HostColumn(pa.array(vals, type=T.to_arrow(dt)), dt)
    if isinstance(dt, T.DecimalType) and dt.is_decimal128:
        import decimal
        vals = [decimal.Decimal(int(tc.data[i])).scaleb(-dt.scale)
                if valid[i] else None for i in range(row_count)]
        return HostColumn(pa.array(vals, type=T.to_arrow(dt)), dt)
    return HostColumn.from_numpy(np.asarray(tc.data)[:row_count],
                                 valid[:row_count], dt)


def _pyify(v, dt):
    if v is None:
        return None
    if hasattr(v, "item"):
        return v.item()
    return v


# ---------------------------------------------------------------------------
# CPU evaluation (fallback + oracle)
# ---------------------------------------------------------------------------

def eval_exprs_cpu(exprs: Sequence[Expression],
                   batch: HostColumnarBatch,
                   names: Optional[List[str]] = None) -> HostColumnarBatch:
    cols = host_batch_tcols(batch)
    ctx = EvalContext(cols, "cpu", batch.row_count)
    outs = [e.eval_cpu(ctx) for e in exprs]
    host_cols = [tcol_to_host_column(tc, batch.row_count) for tc in outs]
    return HostColumnarBatch(host_cols, batch.row_count,
                             names or _out_names(exprs))


# ---------------------------------------------------------------------------
# TPU evaluation: one jitted XLA program per (plan signature, schema, bucket)
# (programs live in the process-wide StageCompiler cache, exec/stage_compiler)
# ---------------------------------------------------------------------------


def _signature(exprs, batch: ColumnarBatch) -> Tuple:
    shape_sig = tuple(
        (str(c.data_type), tuple(c.data.shape),
         None if c.lengths is None else True,
         None if c.elem_valid is None else True)
        for c in batch.columns)
    # sql() alone under-identifies (lit(1, INT) and lit(1, LONG) both
    # render "1"; a named reference renders its name at any ordinal), so
    # the output dtype and the ordinals read participate in the key
    return (tuple(expr_key(e) for e in exprs), shape_sig)


def eval_exprs_tpu(exprs: Sequence[Expression], batch: ColumnarBatch,
                   names: Optional[List[str]] = None,
                   kind: str = "expr.project") -> ColumnarBatch:
    """``kind`` names the program for the stage compiler's counters and
    for the device trace (``jit_run_<kind>``)."""
    from spark_rapids_tpu.columnar.column import _jnp
    from spark_rapids_tpu.columnar.encoding import (batch_has_encoded,
                                                    materialize_batch)
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    if batch_has_encoded(batch):
        # decode only the ordinals these expressions actually read; an
        # unreferenced encoded column would still flow into the program
        # below as raw codes, so it must decode too unless every
        # expression ignores it (projections list their inputs)
        from spark_rapids_tpu.expressions.base import BoundReference
        refs = set()
        for e in exprs:
            refs.update(b.ordinal for b in
                        e.collect(lambda n: isinstance(n, BoundReference)))
        batch = materialize_batch(batch, ordinals=sorted(refs),
                                  site="operator")
    xp = _jnp()
    key = _signature(exprs, batch)
    dtypes = [c.data_type for c in batch.columns]
    bucket = batch.bucket

    def build():
        def run(arrs):
            cols = [TCol(d, v, dt, lengths=ln, elem_valid=ev)
                    for (d, v, ln, ev), dt in zip(arrs, dtypes)]
            ctx = EvalContext(cols, "tpu", bucket)
            outs = []
            for e in exprs:
                tc = e.eval_tpu(ctx)
                dc = tcol_to_device_column(tc, 0, bucket, xp)
                outs.append((dc.data, dc.validity, dc.lengths,
                             dc.elem_valid))
            return outs
        return run

    fn = get_or_build(kind, key, build)

    arrs = [(c.data, c.validity, c.lengths, c.elem_valid)
            for c in batch.columns]
    results = fn(arrs)
    out_cols = []
    for (d, v, ln, ev), e in zip(results, exprs):
        out_cols.append(DeviceColumn(d, v, batch.row_count, e.data_type,
                                     lengths=ln, elem_valid=ev))
    return ColumnarBatch(out_cols, batch.row_count, names or _out_names(exprs))


def _out_names(exprs) -> List[str]:
    from spark_rapids_tpu.expressions.base import Alias, BoundReference
    names = []
    for i, e in enumerate(exprs):
        if isinstance(e, Alias):
            names.append(e.alias_name)
        elif isinstance(e, BoundReference) and e.ref_name:
            names.append(e.ref_name)
        else:
            names.append(f"col{i}")
    return names
