"""Comparison and boolean predicates (reference: org/apache/spark/sql/rapids/
predicates.scala — GpuAnd/GpuOr/GpuNot; GpuEqualTo etc. in GpuOverrides
expr registrations; nullExpressions.scala — GpuIsNull/GpuIsNotNull/GpuCoalesce;
NormalizeFloatingNumbers handling of NaN comparisons).

Spark semantics implemented here:
- Comparisons propagate NULL; EqualNullSafe (<=>) never returns NULL.
- AND/OR use Kleene three-valued logic (FALSE AND NULL = FALSE).
- NaN: Spark treats NaN = NaN as TRUE and NaN greater than everything in
  comparisons (unlike IEEE); see docs/compatibility.md in the reference.
"""

from __future__ import annotations

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions.base import (Expression, EvalContext, TCol,
                                               both_valid, jnp, materialize,
                                               valid_array)
from spark_rapids_tpu.expressions.arithmetic import BinaryExpr, UnaryExpr


def _compare_dtype(left: Expression, right: Expression) -> T.DataType:
    lt, rt = left.data_type, right.data_type
    if lt == rt:
        return lt
    return T.common_type(lt, rt)


def _string_cmp_arrays(c: TCol, ctx: EvalContext, xp):
    """Device strings compare bytewise on the padded rectangle; padding is
    zero so prefix ordering matches byte-lexicographic ordering for UTF-8."""
    return c.data, c.lengths


class BinaryComparison(BinaryExpr):
    @property
    def data_type(self):
        return T.BOOLEAN

    def _cmp(self, a, b, xp):
        raise NotImplementedError

    # string comparison on device: compare padded byte rows lexicographically
    def _device_string_cmp(self, a: TCol, b: TCol, xp):
        ad, bd = a.data, b.data
        w = max(ad.shape[1], bd.shape[1])
        if ad.shape[1] < w:
            ad = xp.pad(ad, ((0, 0), (0, w - ad.shape[1])))
        if bd.shape[1] < w:
            bd = xp.pad(bd, ((0, 0), (0, w - bd.shape[1])))
        # first differing byte decides; equal prefixes decided by length
        diff = ad.astype(np.int16) - bd.astype(np.int16)
        nz = diff != 0
        first_idx = xp.argmax(nz, axis=1)
        any_nz = xp.any(nz, axis=1)
        first = xp.take_along_axis(diff, first_idx[:, None], axis=1)[:, 0]
        cmp = xp.where(any_nz, xp.sign(first),
                       xp.sign(a.lengths - b.lengths))
        return cmp  # -1/0/1 per row

    def tpu_supported(self, conf):
        from spark_rapids_tpu.expressions import decimal_math as DM
        lt, rt = self.left.data_type, self.right.data_type
        if DM.compare_involved(lt, rt):
            return DM.compare_supported(lt, rt)
        return None

    def _eval(self, ctx: EvalContext, xp) -> TCol:
        from spark_rapids_tpu.expressions import decimal_math as DM
        a = self.left.eval(ctx)
        b = self.right.eval(ctx)
        # Spark promotes decimal-vs-fractional to double before comparing
        if isinstance(a.dtype, T.DecimalType) and b.dtype.is_floating:
            a = _decimal_side_to_double(a, ctx, xp)
        elif isinstance(b.dtype, T.DecimalType) and a.dtype.is_floating:
            b = _decimal_side_to_double(b, ctx, xp)
        valid = both_valid(a, b, ctx)
        if DM.compare_involved(a.dtype, b.dtype) and \
                not (a.is_scalar and b.is_scalar):
            cmp = DM.compare(a, b, ctx, xp)
            out = self._cmp(cmp, np.int8(0), xp)
            if isinstance(valid, bool):
                from spark_rapids_tpu.expressions.base import valid_array
                valid = valid_array(a, ctx) & valid_array(b, ctx)
            return TCol(out, valid, T.BOOLEAN)
        if a.is_scalar and b.is_scalar:
            if not valid:
                return TCol.scalar(None, T.BOOLEAN)
            if DM.compare_involved(a.dtype, b.dtype):
                x = DM._scalar_unscaled(a) * 10 ** max(
                    0, _dscale(b.dtype) - _dscale(a.dtype))
                y = DM._scalar_unscaled(b) * 10 ** max(
                    0, _dscale(a.dtype) - _dscale(b.dtype))
                order = (x > y) - (x < y)
                return TCol.scalar(bool(self._cmp(np.asarray(order),
                                                  np.asarray(0), np)[()]),
                                   T.BOOLEAN)
            return TCol.scalar(bool(self._cmp(np.asarray(a.data),
                                              np.asarray(b.data), np)[()]),
                               T.BOOLEAN)
        if ctx.backend == "tpu" and (a.is_string or b.is_string):
            a, b = (_densify_string(a, ctx, xp, like=b),
                    _densify_string(b, ctx, xp, like=a))
            cmp = self._device_string_cmp(a, b, xp)
            out = self._cmp(cmp, xp.zeros_like(cmp), xp)
            return TCol(out, valid, T.BOOLEAN)
        ad = materialize(a, ctx)
        bd = materialize(b, ctx)
        if ctx.backend == "cpu" and (a.is_string or b.is_string):
            # object arrays: python comparison row-wise, vectorized via numpy
            with np.errstate(all="ignore"):
                out = self._cmp_obj(ad, bd)
            return TCol(out, valid, T.BOOLEAN)
        ad, bd = _numeric_align(ad, bd, xp)
        out = self._cmp(ad, bd, xp)
        return TCol(out, valid, T.BOOLEAN)

    def _cmp_obj(self, ad, bd):
        n = len(ad)
        out = np.zeros(n, dtype=bool)
        for i in range(n):
            x, y = ad[i], bd[i]
            if x is None or y is None:
                continue
            out[i] = bool(self._cmp(np.asarray(x), np.asarray(y), np)[()]) \
                if not isinstance(x, str) else self._py_cmp(x, y)
        return out

    def _py_cmp(self, x, y):
        order = (x > y) - (x < y)
        return bool(self._cmp(np.asarray(order), np.asarray(0), np)[()])

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    def eval_cpu(self, ctx):
        with np.errstate(all="ignore"):
            return self._eval(ctx, np)


def _dscale(dt) -> int:
    return dt.scale if isinstance(dt, T.DecimalType) else 0


def _decimal_side_to_double(c: TCol, ctx, xp) -> TCol:
    from spark_rapids_tpu.expressions import decimal_math as DM
    if c.is_scalar:
        v = None if c.data is None else \
            float(DM._scalar_unscaled(c)) / (10.0 ** c.dtype.scale)
        return TCol.scalar(v, T.DOUBLE)
    return DM.decimal_to_double(c, ctx, xp)


def string_literal_planes(value, width: int = None):
    """A scalar string (or binary) as the device holds one row of a string
    column: its bytes zero-padded to ``width`` (the bucket of its own
    length where none is given) and its length in bytes."""
    raw = np.frombuffer(value.encode() if isinstance(value, str) else value,
                        dtype=np.uint8)
    if width is None:
        from spark_rapids_tpu.columnar.column import bucket_strlen
        width = bucket_strlen(max(1, len(raw)))
    chars = np.zeros(width, dtype=np.uint8)
    chars[:len(raw)] = raw
    return chars, np.asarray(len(raw), dtype=np.int32)


def _densify_string(c: TCol, ctx: EvalContext, xp, like: TCol = None):
    """A scalar string as a per-row one: its bytes broadcast over the rows
    inside the program, so the program holds the bytes once (or takes
    them as an argument: a promoted literal, plan/stages.py) and never a
    ``(row_count, width)`` constant.  Beside a per-row string ``like`` the
    bytes take that column's width: a longer scalar keeps its true length
    and the bytes the column can hold, which orders and tells it apart
    from every value of the column as the whole would."""
    if not c.is_scalar:
        return c
    if c.lengths is not None:       # promoted: traced bytes and length
        chars, length = c.data, c.lengths
    else:
        chars, length = string_literal_planes(c.data or "")
    if like is not None and like.is_string and not like.is_scalar:
        w = like.data.shape[1]
        chars = chars[:w] if chars.shape[0] >= w else \
            xp.pad(chars, (0, w - chars.shape[0]))
    rows = ctx.row_count
    return TCol(xp.broadcast_to(xp.asarray(chars)[None, :],
                                (rows, chars.shape[0])),
                valid_array(c, ctx), c.dtype,
                lengths=xp.full(rows, length, dtype=np.int32))


def _numeric_align(ad, bd, xp):
    """Promotes both arrays to a common numeric dtype for comparison."""
    if ad.dtype == bd.dtype:
        return ad, bd
    common = np.promote_types(ad.dtype, bd.dtype)
    return ad.astype(common), bd.astype(common)


class EqualTo(BinaryComparison):
    symbol = "="

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            # Spark: NaN = NaN is TRUE
            return (a == b) | (xp.isnan(a) & xp.isnan(b))
        return a == b


class LessThan(BinaryComparison):
    symbol = "<"

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            # Spark: NaN is greater than everything
            return (a < b) | (xp.isnan(b) & ~xp.isnan(a))
        return a < b


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            return (a <= b) | xp.isnan(b)
        return a <= b


class GreaterThan(BinaryComparison):
    symbol = ">"

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            return (a > b) | (xp.isnan(a) & ~xp.isnan(b))
        return a > b


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            return (a >= b) | xp.isnan(a)
        return a >= b


class NotEqual(BinaryComparison):
    symbol = "!="

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            return ~((a == b) | (xp.isnan(a) & xp.isnan(b)))
        return a != b


class EqualNullSafe(BinaryComparison):
    """<=> : nulls compare equal; never returns NULL."""
    symbol = "<=>"

    def _cmp(self, a, b, xp):
        if a.dtype.kind == "f":
            return (a == b) | (xp.isnan(a) & xp.isnan(b))
        return a == b

    def _eval(self, ctx, xp):
        a = self.left.eval(ctx)
        b = self.right.eval(ctx)
        if a.is_scalar and b.is_scalar:
            an, bn = a.data is None, b.data is None
            if an or bn:
                return TCol.scalar(an and bn, T.BOOLEAN)
            return super()._eval(ctx, xp)
        base = super()._eval(ctx, xp)
        av = valid_array(a, ctx)
        bv = valid_array(b, ctx)
        eq = xp.asarray(base.data) & av & bv
        both_null = ~av & ~bv
        return TCol(eq | both_null, xp.ones_like(av), T.BOOLEAN)


class And(BinaryExpr):
    """Kleene AND: F&x=F, T&N=N."""
    symbol = "AND"

    @property
    def data_type(self):
        return T.BOOLEAN

    def _eval(self, ctx, xp):
        a = self.left.eval(ctx)
        b = self.right.eval(ctx)
        if a.is_scalar and b.is_scalar:
            av = a.data if a.valid else None
            bv = b.data if b.valid else None
            if av is False or bv is False:
                return TCol.scalar(False, T.BOOLEAN)
            if av is None or bv is None:
                return TCol.scalar(None, T.BOOLEAN)
            return TCol.scalar(True, T.BOOLEAN)
        ad = materialize(a, ctx, np.dtype(bool))
        bd = materialize(b, ctx, np.dtype(bool))
        av = valid_array(a, ctx)
        bv = valid_array(b, ctx)
        at = ad & av  # definitely true
        bt = bd & bv
        af = ~ad & av  # definitely false
        bf = ~bd & bv
        out = at & bt
        valid = (at & bt) | af | bf
        return TCol(out, valid, T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class Or(BinaryExpr):
    """Kleene OR: T|x=T, F|N=N."""
    symbol = "OR"

    @property
    def data_type(self):
        return T.BOOLEAN

    def _eval(self, ctx, xp):
        a = self.left.eval(ctx)
        b = self.right.eval(ctx)
        if a.is_scalar and b.is_scalar:
            av = a.data if a.valid else None
            bv = b.data if b.valid else None
            if av is True or bv is True:
                return TCol.scalar(True, T.BOOLEAN)
            if av is None or bv is None:
                return TCol.scalar(None, T.BOOLEAN)
            return TCol.scalar(False, T.BOOLEAN)
        ad = materialize(a, ctx, np.dtype(bool))
        bd = materialize(b, ctx, np.dtype(bool))
        av = valid_array(a, ctx)
        bv = valid_array(b, ctx)
        at = ad & av
        bt = bd & bv
        out = at | bt
        valid = at | bt | (av & bv)
        return TCol(out, valid, T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class Not(UnaryExpr):
    @property
    def data_type(self):
        return T.BOOLEAN

    def _eval(self, ctx, xp):
        c = self.child.eval(ctx)
        if c.is_scalar:
            v = c.data if c.valid else None
            return TCol.scalar(None if v is None else not v, T.BOOLEAN)
        return TCol(~c.data, c.valid, T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class IsNull(UnaryExpr):
    @property
    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def _eval(self, ctx, xp):
        c = self.child.eval(ctx)
        if c.is_scalar:
            return TCol.scalar(not bool(c.valid) or c.data is None, T.BOOLEAN)
        ones = xp.ones_like(c.valid)
        return TCol(~c.valid, ones, T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class IsNotNull(UnaryExpr):
    @property
    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def _eval(self, ctx, xp):
        c = self.child.eval(ctx)
        if c.is_scalar:
            return TCol.scalar(bool(c.valid) and c.data is not None, T.BOOLEAN)
        ones = xp.ones_like(c.valid)
        return TCol(c.valid, ones, T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class IsNan(UnaryExpr):
    @property
    def data_type(self):
        return T.BOOLEAN

    def _eval(self, ctx, xp):
        c = self.child.eval(ctx)
        if c.is_scalar:
            import math
            v = c.data if c.valid else None
            return TCol.scalar(False if v is None else math.isnan(v), T.BOOLEAN)
        if c.data.dtype.kind != "f":
            return TCol(xp.zeros_like(c.valid), c.valid, T.BOOLEAN)
        return TCol(xp.isnan(c.data) & c.valid, xp.ones_like(c.valid), T.BOOLEAN)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu


class In(Expression):
    """value IN (literals...) — device impl is an OR-reduction of equality
    against each list element (reference GpuInSet uses a cuDF table lookup;
    an OR chain fuses fine in XLA for modest list sizes)."""

    def __init__(self, value: Expression, options):
        super().__init__([value])
        self.options = list(options)

    @property
    def data_type(self):
        return T.BOOLEAN

    def _eval(self, ctx, xp):
        from spark_rapids_tpu.expressions.base import Literal
        c = self.children[0]
        acc = None
        for opt in self.options:
            eq = EqualTo(c, opt if isinstance(opt, Expression) else Literal(opt))
            acc = eq if acc is None else Or(acc, eq)
        if acc is None:
            return TCol.scalar(False, T.BOOLEAN)
        return acc.eval(ctx)

    def eval_tpu(self, ctx):
        return self._eval(ctx, jnp())

    eval_cpu = eval_tpu
