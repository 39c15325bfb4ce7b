"""Expression base classes and evaluation contexts.

Reference: the GpuExpression hierarchy (sql-plugin/.../GpuExpressions.scala)
and Spark Catalyst's Expression tree.  Two evaluation paths:

- ``eval_tpu(ctx)``: builds jax ops on ``TCol`` values.  Called inside a
  traced function, so the whole tree compiles into one XLA program and XLA
  fuses everything (TPU-first whole-stage fusion).
- ``eval_cpu(ctx)``: independent numpy/pyarrow implementation with the same
  SQL semantics; the CPU fallback path and the differential-test oracle.

Value representations:
- TPU: ``TCol(data, valid, dtype, lengths)`` of jax arrays.  Strings are
  uint8[bucket, width] + lengths.  Scalars use ``is_scalar=True`` with
  python/0-d values (broadcast lazily by kernels).
- CPU: ``TCol`` of numpy arrays; strings are object arrays of ``str``.

SQL null semantics: every value carries ``valid``; kernels must propagate
nulls per-operator (null-propagating by default; Kleene logic for AND/OR).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import types as T


def jnp():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


@dataclasses.dataclass
class TCol:
    """A columnar value during evaluation (device or host backend)."""
    data: Any
    valid: Any                 # bool array, or True/False for scalars
    dtype: T.DataType
    lengths: Any = None        # string/array columns (device rep)
    is_scalar: bool = False
    elem_valid: Any = None     # array columns only (device rep)

    @staticmethod
    def scalar(value, dtype: T.DataType) -> "TCol":
        return TCol(value, value is not None, dtype, is_scalar=True)

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, (T.StringType, T.BinaryType))


class EvalContext:
    """Holds the input columns for BoundReference + backend selector.

    ``row_count`` is the PHYSICAL length of the column arrays — the padded
    bucket on the device backend, the logical row count on the CPU backend.
    Kernels always produce physical-length outputs; the exec layer tracks the
    logical count and masks padding via validity.
    """

    __slots__ = ("cols", "backend", "row_count", "lambda_bindings",
                 "elem_plane", "literal_args", "enc_tables")

    def __init__(self, cols: Sequence[TCol], backend: str, row_count: int):
        self.cols = list(cols)
        self.backend = backend  # "tpu" | "cpu"
        self.row_count = row_count
        self.lambda_bindings = {}  # name -> TCol (higher-order functions)
        #: True while evaluating a lambda body over an [n, w] element plane
        #: (scalars then densify to [n, 1] so they broadcast either way)
        self.elem_plane = False
        #: runtime values for PromotedLiteral slots (plan/stages.py) when
        #: evaluating inside a parameterized fused-stage trace
        self.literal_args = None
        #: device bool lookup tables for code-space dictionary predicates
        #: (columnar/encoding.py DictContains slots)
        self.enc_tables = None


class Expression:
    """Base expression node."""

    #: False for expressions that must not be constant-folded even over
    #: all-literal children (aggregation/window context dependence).
    foldable: bool = True
    #: False for expressions whose value differs per evaluation (rand,
    #: uuid, monotonically_increasing_id).  fold_constants refuses to fold
    #: these regardless of ``foldable`` — any new non-deterministic
    #: expression MUST set this or it would silently fold to one literal.
    deterministic: bool = True

    def __init__(self, children: Sequence["Expression"] = ()):
        self.children: List[Expression] = list(children)

    # -- static info --------------------------------------------------------
    @property
    def data_type(self) -> T.DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children) if self.children else True

    @property
    def name(self) -> str:
        return type(self).__name__

    def sql(self) -> str:
        args = ", ".join(c.sql() for c in self.children)
        return f"{self.name}({args})"

    # -- evaluation ---------------------------------------------------------
    def eval(self, ctx: EvalContext) -> TCol:
        if ctx.backend == "tpu":
            return self.eval_tpu(ctx)
        return self.eval_cpu(ctx)

    def eval_tpu(self, ctx: EvalContext) -> TCol:
        raise NotImplementedError(f"{self.name}.eval_tpu")

    def eval_cpu(self, ctx: EvalContext) -> TCol:
        raise NotImplementedError(f"{self.name}.eval_cpu")

    # -- planner hooks ------------------------------------------------------
    def tpu_supported(self, conf) -> Optional[str]:
        """None if supported on device; else a reason string (used by the
        meta layer to tag fallback, reference RapidsMeta.willNotWorkOnGpu)."""
        return None

    def transform_up(self, fn: Callable[["Expression"], "Expression"]) -> "Expression":
        node = self.with_children([c.transform_up(fn) for c in self.children])
        return fn(node)

    def with_children(self, children: List["Expression"]) -> "Expression":
        if not self.children and not children:
            return self
        import copy
        node = copy.copy(self)
        node.children = list(children)
        return node

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def over(self, spec) -> "Expression":
        """agg_function.over(window_spec) -> WindowExpression (valid for
        aggregate functions; ranking functions override on their class)."""
        from spark_rapids_tpu.expressions.window_exprs import (
            WindowExpression, _to_spec)
        if not getattr(self, "is_aggregate", False):
            raise TypeError(f"{self.name} cannot be used as a window "
                            "function")
        return WindowExpression(self, _to_spec(spec))

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children:
            out.extend(c.collect(pred))
        return out

    def __repr__(self):
        return self.sql()

    # -- pyspark-Column-style operator sugar --------------------------------
    @staticmethod
    def _wrap(v) -> "Expression":
        return v if isinstance(v, Expression) else lit(v)

    def _bin(self, other, cls, flip=False):
        a, b = Expression._wrap(other), self
        if not flip:
            a, b = b, a
        return cls(a, b)

    def __add__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Add
        return self._bin(o, Add)

    def __radd__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Add
        return self._bin(o, Add, True)

    def __sub__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Subtract
        return self._bin(o, Subtract)

    def __rsub__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Subtract
        return self._bin(o, Subtract, True)

    def __mul__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Multiply
        return self._bin(o, Multiply)

    def __rmul__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Multiply
        return self._bin(o, Multiply, True)

    def __truediv__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Divide
        return self._bin(o, Divide)

    def __rtruediv__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Divide
        return self._bin(o, Divide, True)

    def __mod__(self, o):
        from spark_rapids_tpu.expressions.arithmetic import Remainder
        return self._bin(o, Remainder)

    def __neg__(self):
        from spark_rapids_tpu.expressions.arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __lt__(self, o):
        from spark_rapids_tpu.expressions.predicates import LessThan
        return self._bin(o, LessThan)

    def __le__(self, o):
        from spark_rapids_tpu.expressions.predicates import LessThanOrEqual
        return self._bin(o, LessThanOrEqual)

    def __gt__(self, o):
        from spark_rapids_tpu.expressions.predicates import GreaterThan
        return self._bin(o, GreaterThan)

    def __ge__(self, o):
        from spark_rapids_tpu.expressions.predicates import GreaterThanOrEqual
        return self._bin(o, GreaterThanOrEqual)

    def __eq__(self, o):
        from spark_rapids_tpu.expressions.predicates import EqualTo
        return self._bin(o, EqualTo)

    def __ne__(self, o):
        from spark_rapids_tpu.expressions.predicates import NotEqual
        return self._bin(o, NotEqual)

    __hash__ = object.__hash__  # __eq__ builds an expression, not a bool

    def __bool__(self):
        raise ValueError(
            "Cannot convert an Expression to a bool: use '&' for AND, '|' "
            "for OR, '~' for NOT, and avoid chained comparisons "
            "(a < col < b)")

    def __and__(self, o):
        from spark_rapids_tpu.expressions.predicates import And
        return self._bin(o, And)

    def __or__(self, o):
        from spark_rapids_tpu.expressions.predicates import Or
        return self._bin(o, Or)

    def __invert__(self):
        from spark_rapids_tpu.expressions.predicates import Not
        return Not(self)


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class Literal(Expression):
    def __init__(self, value, dtype: Optional[T.DataType] = None):
        super().__init__()
        self.value = value
        self._dtype = dtype or _infer_literal_type(value)

    @property
    def data_type(self) -> T.DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    def sql(self):
        return repr(self.value)

    def _as_tcol(self) -> TCol:
        return TCol.scalar(self.value, self._dtype)

    def eval_tpu(self, ctx):
        return self._as_tcol()

    def eval_cpu(self, ctx):
        return self._as_tcol()


class BoundReference(Expression):
    def __init__(self, ordinal: int, dtype: T.DataType, nullable: bool = True,
                 ref_name: str = ""):
        super().__init__()
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable
        self.ref_name = ref_name

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self._nullable

    def sql(self):
        return self.ref_name or f"input[{self.ordinal}]"

    def eval_tpu(self, ctx):
        return ctx.cols[self.ordinal]

    eval_cpu = eval_tpu


def expr_key(e: Expression) -> tuple:
    """What identifies a bound expression to a program cache: its text,
    its type and the ordinals its references read.  The text alone does
    not: a named reference renders as its name, and two inputs of one
    shape may hold that name at two ordinals (``select l.k, v, w`` over
    ``l`` joined to ``r``, and over ``r`` joined to ``l``)."""
    return (e.sql(), str(e.data_type),
            tuple(b.ordinal for b in
                  e.collect(lambda n: isinstance(n, BoundReference))))


class AttributeReference(Expression):
    """Named column reference, resolved to BoundReference at bind time."""

    def __init__(self, ref_name: str):
        super().__init__()
        self.ref_name = ref_name

    @property
    def data_type(self):
        raise TypeError(f"unresolved attribute {self.ref_name!r}")

    def sql(self):
        return self.ref_name


class Alias(Expression):
    def __init__(self, child: Expression, alias_name: str):
        super().__init__([child])
        self.alias_name = alias_name

    @property
    def data_type(self):
        return self.children[0].data_type

    @property
    def nullable(self):
        return self.children[0].nullable

    def sql(self):
        return f"{self.children[0].sql()} AS {self.alias_name}"

    def eval_tpu(self, ctx):
        return self.children[0].eval(ctx)

    eval_cpu = eval_tpu


def _infer_literal_type(value) -> T.DataType:
    import datetime
    import decimal
    import numpy as _np
    if value is None:
        return T.NULL
    if isinstance(value, _np.generic):
        return T.from_numpy_dtype(value.dtype)
    if isinstance(value, bool):
        return T.BOOLEAN
    if isinstance(value, int):
        return T.INT if -(2**31) <= value < 2**31 else T.LONG
    if isinstance(value, float):
        return T.DOUBLE
    if isinstance(value, str):
        return T.STRING
    if isinstance(value, bytes):
        return T.BINARY
    if isinstance(value, decimal.Decimal):
        sign, digits, exp = value.as_tuple()
        scale = max(0, -exp)
        return T.DecimalType(max(len(digits), scale + 1), scale)
    if isinstance(value, datetime.datetime):
        return T.TIMESTAMP
    if isinstance(value, datetime.date):
        return T.DATE
    raise TypeError(f"cannot infer literal type of {value!r}")


# ---------------------------------------------------------------------------
# Binding & helpers
# ---------------------------------------------------------------------------

def bind_references(expr: Expression, schema: T.StructType) -> Expression:
    """Resolves AttributeReference names to ordinals (reference:
    GpuBindReferences.bindGpuReferences)."""

    def fix(node: Expression) -> Expression:
        if isinstance(node, AttributeReference):
            i = schema.field_index(node.ref_name)
            f = schema.fields[i]
            return BoundReference(i, f.data_type, f.nullable, f.name)
        if hasattr(node, "_sync_var_types"):
            # higher-order fns type their lambda variables once the array
            # child is resolved (the vars are shared leaf instances)
            node._sync_var_types()
        return node

    return expr.transform_up(fix)


def fold_constants(expr: Expression) -> Expression:
    """Evaluates deterministic all-literal subtrees once on the host and
    replaces them with Literals (Spark's ConstantFolding logical rule),
    and simplifies struct CONSTRUCTOR forms so they never need a device
    struct plane (Spark's SimplifyExtractValueOps + struct-equality
    expansion):

    - ``struct(a, b).a``             -> ``a``
    - ``struct(a, b) = struct(c, d)`` -> ``a <=> c AND b <=> d``
      (struct equality is field-wise NULL-SAFE in Spark; the constructor
      itself is never null, so no outer null term is needed)

    First-order device win: ``cast('2000-08-23' as date)`` inside a filter
    otherwise drags the whole operator to host because string->date casts
    are host-only; folded to a DATE literal the comparison stays on device.
    """
    from spark_rapids_tpu.expressions.evaluator import tcol_to_host_column

    def fix(n: Expression) -> Expression:
        simplified = _simplify_struct_node(n)
        if simplified is not None:
            return simplified
        if (isinstance(n, (Literal, Alias)) or not n.children or
                not n.foldable or not n.deterministic or
                not all(isinstance(c, Literal) for c in n.children)):
            return n
        try:
            tc = n.eval_cpu(EvalContext([], "cpu", 1))
            v = tcol_to_host_column(tc, 1).arrow[0].as_py()
            return Literal(v, n.data_type)
        except Exception:  # noqa: BLE001 — any eval failure (overflow,
            # arrow conversion, host-only op) defers to runtime, where the
            # engine's own error surfaces; folding is an optimization and
            # must never turn a runnable plan into a planning error
            return n

    return expr.transform_up(fix)


def _simplify_struct_node(n: Expression):
    """Struct-constructor simplifications (see fold_constants docstring).
    Returns the replacement or None."""
    from spark_rapids_tpu.expressions.collections import (CreateNamedStruct,
                                                          GetStructField)
    from spark_rapids_tpu.expressions import predicates as PR
    if isinstance(n, GetStructField) and \
            isinstance(n.children[0], CreateNamedStruct):
        st = n.children[0]
        # SQL identifiers resolve case-insensitively (Spark default)
        want = n.field_name.lower()
        for nm, child in zip(st.names, st.children):
            if nm.lower() == want:
                return child
        return None   # unknown field: defer to GetStructField's own error
    if isinstance(n, PR.EqualTo):
        l, r = n.children
        if isinstance(l, CreateNamedStruct) and \
                isinstance(r, CreateNamedStruct) and \
                len(l.children) == len(r.children):
            out = None
            for lc, rc in zip(l.children, r.children):
                term = PR.EqualNullSafe(lc, rc)
                out = term if out is None else PR.And(out, term)
            return out
    return None


def col(name: str) -> AttributeReference:
    return AttributeReference(name)


def lit(value, dtype: Optional[T.DataType] = None) -> Literal:
    return Literal(value, dtype)


# -- broadcast/validity helpers shared by kernels ---------------------------

def both_valid(a: TCol, b: TCol, ctx: EvalContext):
    """Combined validity of two inputs; returns array or scalar bool."""
    av, bv = a.valid, b.valid
    if a.is_scalar and b.is_scalar:
        return bool(av) and bool(bv)
    xp = jnp() if ctx.backend == "tpu" else np
    if a.is_scalar:
        return bv if av else xp.zeros_like(bv)
    if b.is_scalar:
        return av if bv else xp.zeros_like(av)
    return av & bv


def all_valid(cols: Sequence[TCol], ctx: EvalContext):
    out = cols[0]
    acc = out.valid
    for c in cols[1:]:
        nxt = TCol(None, acc, out.dtype)
        acc = both_valid(nxt, c, ctx)
    return acc


def to_physical_scalar(v):
    """Date/timestamp python objects -> the physical int representation
    kernels compute on (micros since epoch / days since epoch); any other
    value passes through.  Shared by ``materialize`` (baked constants) and
    plan/stages.physical_literal (promoted runtime args) — the two MUST
    produce identical values or promoted-vs-baked programs diverge."""
    import datetime as _dt
    if isinstance(v, _dt.datetime):
        import calendar
        return int(calendar.timegm(v.utctimetuple())) * 1_000_000 \
            + v.microsecond
    if isinstance(v, _dt.date):
        return (v - _dt.date(1970, 1, 1)).days
    return v


def materialize(c: TCol, ctx: EvalContext, np_dtype=None) -> Any:
    """Densifies a scalar TCol to a full column when a kernel needs arrays."""
    xp = jnp() if ctx.backend == "tpu" else np
    if not c.is_scalar:
        return c.data
    dt = np_dtype or (c.dtype.np_dtype or np.dtype(object))
    shape = (ctx.row_count, 1) if ctx.elem_plane else (ctx.row_count,)
    if c.data is None:
        if dt == np.dtype(object):
            return np.full(shape, None, dtype=object)
        return xp.zeros(shape, dtype=dt)
    if dt == np.dtype(object):
        return np.full(shape, c.data, dtype=object)
    # date/timestamp literals carry python objects; kernels want the
    # physical int representation
    v = to_physical_scalar(c.data)
    return xp.full(shape, v, dtype=dt)


def valid_array(c: TCol, ctx: EvalContext):
    xp = jnp() if ctx.backend == "tpu" else np
    if not c.is_scalar:
        return c.valid
    shape = (ctx.row_count, 1) if ctx.elem_plane else (ctx.row_count,)
    return xp.full(shape, bool(c.valid), dtype=bool)
