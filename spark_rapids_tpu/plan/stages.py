"""Whole-stage compilation planner pass.

Walks maximal device-side operator pipelines between exchange /
materialization boundaries — filter/project chains, the hash aggregate's
update pass (and, in the exec, its merge+final pass), sort-key prep —
and lowers each stage to ONE compiled XLA program (exec/fused.py,
programs cached process-wide by exec/stage_compiler).  This is the
engine's analog of Spark's whole-stage codegen and of Flare's
whole-query native compilation (PAPERS.md): the reference dispatches one
cuDF kernel per operator and cannot fuse across them; a tracing compiler
makes cross-operator fusion a plan rewrite.

**Literal promotion** (conf ``spark.rapids.sql.compile.literalPromotion``):
scalar literals in fused chains are promoted to RUNTIME ARGUMENTS of the
compiled program, so ``d_year = 1998`` and ``d_year = 1999`` — or a
dashboard's parameterized date ranges — share one executable instead of
compiling per value.  Program cache keys stay bounded by plan SHAPE, not
by literal cardinality.  Promotion is deliberately conservative: only
literals sitting directly under comparison / +,-,* arithmetic whose
sibling operand has the SAME data type are promoted (same-dtype operands
make the strong-typed runtime scalar bit-identical to the weak-typed
baked constant; mixed-dtype promotions could shift XLA's promotion rules
and break the bit-identical-vs-CPU contract).  A string literal is
promoted under a comparison against a per-row string: it travels as
``PROMOTED_STRING_WIDTH`` zero-padded bytes plus its length, so the
program depends on the column's width alone and ``cd_gender = 'M'`` and
``cd_gender = 'F'`` (or a template's seven education values) share one
executable.
"""

from __future__ import annotations

from typing import List, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions.base import Expression, Literal, TCol
from spark_rapids_tpu.plan.base import Exec

#: synced from spark.rapids.sql.compile.literalPromotion by
#: TpuOverrides.apply (stage fusion itself is gated in the planner on the
#: session conf directly)
LITERAL_PROMOTION = True


class PromotedLiteral(Literal):
    """A literal hoisted out of a fused stage's compiled program: its
    ``sql()`` renders a slot placeholder (so the program cache key is
    value-independent) and ``eval_tpu`` reads the value from the trace's
    runtime-argument list.  Outside a parameterized trace (CPU oracle,
    unfused re-planning) it degrades to a plain literal."""

    def __init__(self, value, dtype, slot: int):
        super().__init__(value, dtype)
        self.slot = slot

    def sql(self):
        return f"$lit{self.slot}:{self._dtype}"

    def eval_tpu(self, ctx):
        vals = getattr(ctx, "literal_args", None)
        if vals is None:
            return self._as_tcol()
        if isinstance(self._dtype, T.StringType):
            # a scalar whose bytes and length are traced values: only a
            # comparison's ``_densify_string`` reads it (``eligible``)
            chars, length = vals[self.slot]
            return TCol(chars, True, self._dtype, lengths=length,
                        is_scalar=True)
        return TCol(vals[self.slot], True, self._dtype, is_scalar=True)


#: bytes a promoted string literal travels in, whatever its own length
#: (a longer literal stays a constant of its program)
PROMOTED_STRING_WIDTH = 64


def physical_literal(value, dtype):
    """The runtime-argument form of a promoted literal: a strongly-typed
    numpy scalar in the column's physical representation (date -> days,
    timestamp -> micros) — exactly what ``materialize`` bakes for the
    constant form (one shared conversion), so the compiled math is
    identical.  A string is its UTF-8 bytes, zero-padded to
    ``PROMOTED_STRING_WIDTH``, and its length in bytes."""
    import numpy as np
    if isinstance(dtype, T.StringType):
        from spark_rapids_tpu.expressions.predicates import \
            string_literal_planes
        return string_literal_planes(value, PROMOTED_STRING_WIDTH)
    from spark_rapids_tpu.expressions.base import to_physical_scalar
    return np.asarray(to_physical_scalar(value), dtype=dtype.np_dtype)


def _promotable_parents():
    """(comparisons, arithmetic): a string promotes under the first only."""
    from spark_rapids_tpu.expressions import arithmetic as A
    from spark_rapids_tpu.expressions import predicates as P
    return ((P.EqualTo, P.NotEqual, P.LessThan, P.LessThanOrEqual,
             P.GreaterThan, P.GreaterThanOrEqual, P.EqualNullSafe),
            (A.Add, A.Subtract, A.Multiply))


_PROMOTABLE_TYPES = (T.ByteType, T.ShortType, T.IntegerType, T.LongType,
                     T.FloatType, T.DoubleType, T.DateType, T.TimestampType)


def promote_stage_literals(ops) -> Tuple[list, List[PromotedLiteral]]:
    """Rewrites a fused stage's op chain, swapping eligible literals for
    ``PromotedLiteral`` slots.  Returns (new ops, promoted literals in
    slot order).  Idempotent over already-promoted chains (re-fusion
    renumbers the slots from the carried values)."""
    comparisons, arithmetic = _promotable_parents()
    promoted: List[PromotedLiteral] = []

    def has_input(e: Expression) -> bool:
        """The subtree evaluates per-row (carries a column / lambda-var
        reference), not to a python scalar."""
        if type(e) in (Literal, PromotedLiteral):
            return False
        if not e.children:
            return True     # column ref / bound ref / lambda variable
        return any(has_input(c) for c in e.children)

    def eligible(lit: Expression, sibling: Expression,
                 parent: Expression) -> bool:
        if type(lit) not in (Literal, PromotedLiteral) or lit.value is None:
            return False
        dt = lit.data_type
        if isinstance(dt, T.StringType):
            if not isinstance(parent, comparisons) or \
                    not isinstance(lit.value, str) or \
                    len(lit.value.encode()) > PROMOTED_STRING_WIDTH:
                return False
        elif not isinstance(dt, _PROMOTABLE_TYPES) or \
                getattr(dt, "np_dtype", None) is None:
            return False
        if not has_input(sibling):
            # literal-vs-literal: the scalar-scalar eval branches run
            # python-level ops (bool()/np.asarray()) that a traced 0-d
            # runtime arg would crash; leave pure-constant math baked
            return False
        try:
            return str(sibling.data_type) == str(dt)
        except Exception:  # noqa: BLE001 — unresolved sibling: skip
            return False

    def walk(e: Expression) -> Expression:
        kids = [walk(c) for c in e.children]
        if isinstance(e, comparisons + arithmetic) and len(kids) == 2:
            for i in (0, 1):
                if eligible(kids[i], kids[1 - i], e):
                    pl = PromotedLiteral(kids[i].value, kids[i].data_type,
                                         len(promoted))
                    promoted.append(pl)
                    kids[i] = pl
        return e.with_children(kids)

    new_ops = []
    for kind, payload in ops:
        if kind == "filter":
            new_ops.append(("filter", walk(payload)))
        else:
            new_ops.append(("project", [walk(p) for p in payload]))
    return new_ops, promoted


def fuse_device_stages(plan: Exec) -> Exec:
    """Whole-stage fusion pass: collapse maximal chains of device narrow
    ops (Filter/Project) — and, when they feed a hash aggregate, the
    aggregate's update pass — into ONE compiled XLA program
    (exec/fused.py).  The reference cannot do this — cuDF dispatches one
    kernel per operator; XLA's tracing model makes cross-operator fusion
    a plan rewrite."""
    from spark_rapids_tpu.exec.aggregate import (FINAL, TpuHashAggregateExec)
    from spark_rapids_tpu.exec.basic import (TpuFilterExec,
                                             TpuFilterProjectExec,
                                             TpuProjectExec)
    from spark_rapids_tpu.exec.fused import (TpuFusedAggExec,
                                             TpuFusedStageExec)

    def promote(ops):
        if not LITERAL_PROMOTION:
            return ops, []
        return promote_stage_literals(ops)

    def chain_of(node: Exec):
        """Descends through fusable narrow ops; returns (ops top-down ->
        bottom-up reversed, base child)."""
        ops = []
        cur = node
        while True:
            if isinstance(cur, TpuFilterExec):
                ops.append(("filter", cur.condition))
                cur = cur.children[0]
            elif isinstance(cur, TpuProjectExec):
                ops.append(("project", cur.exprs))
                cur = cur.children[0]
            elif isinstance(cur, TpuFilterProjectExec):
                ops.append(("project", cur.exprs))
                ops.append(("filter", cur.condition))
                cur = cur.children[0]
            elif isinstance(cur, TpuFusedStageExec):
                ops.extend(reversed(cur.ops))
                cur = cur.children[0]
            else:
                return list(reversed(ops)), cur

    def fix(node: Exec) -> Exec:
        if isinstance(node, TpuHashAggregateExec) and node.mode != FINAL \
                and not node._has_collect():
            # variable-length (collect) buffers run the dedicated
            # segmented_collect path in the exec, not the fused kernel
            ops, base = chain_of(node.children[0])
            ops, lits = promote(ops)
            lay = node.layout
            return TpuFusedAggExec(ops, lay, node.mode, base, promoted=lits)
        if isinstance(node, (TpuFilterExec, TpuProjectExec,
                             TpuFilterProjectExec)):
            ops, base = chain_of(node)
            # fuse whenever it saves a dispatch: any filter (eager predicate
            # + separate compact otherwise) or a multi-op chain
            if len(ops) >= 2 or any(k == "filter" for k, _ in ops):
                ops, lits = promote(ops)
                return TpuFusedStageExec(ops, base, promoted=lits)
        return node

    return plan.transform_up(fix)
