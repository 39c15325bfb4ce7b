"""SQL analyzer: AST -> physical plan over the engine's exec nodes.

The reference delegates parsing/analysis to Spark Catalyst and only rewrites
physical plans (GpuOverrides.scala:4562); this standalone engine analyzes
its own AST.  Capabilities:

- name resolution with table qualifiers and aliases over scopes
- star-schema join-graph construction: comma-joined relations + WHERE
  equi-conjuncts become a greedy join tree with single-table predicates
  pushed below the joins (Catalyst's PushPredicateThroughJoin +
  ReorderJoin, simplified)
- aggregate planning with HAVING/hidden aggregates, ROLLUP/CUBE
- subqueries:
  * uncorrelated scalar -> evaluated eagerly, inlined as a literal
  * correlated scalar (equality-correlated aggregate) -> decorrelated to
    a grouped aggregate LEFT-joined on the correlation keys
  * top-level [NOT] EXISTS / IN (subquery) conjuncts -> semi/anti joins
  * nested (OR-composed) EXISTS/IN -> existence-marker LEFT joins
    (the reference's existence join, GpuHashJoin existence variants)
- window functions over the engine's WindowExpression machinery
- set operations, DISTINCT, ORDER BY (ordinals/aliases/hidden columns),
  LIMIT

Known deviation (documented in docs/compatibility.md): NOT IN (subquery)
uses plain anti-join semantics; Spark's null-aware anti join differs when
the subquery returns NULLs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expressions import arithmetic as AR
from spark_rapids_tpu.expressions import conditional as CO
from spark_rapids_tpu.expressions import predicates as PR
from spark_rapids_tpu.expressions import strings as ST
from spark_rapids_tpu.expressions import datetime_exprs as DT
from spark_rapids_tpu.expressions import mathexprs as MA
from spark_rapids_tpu.expressions import aggregates as AG
from spark_rapids_tpu.expressions import window_exprs as WX
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression, Literal, lit)
from spark_rapids_tpu.expressions.cast import Cast
from spark_rapids_tpu.sql import ast as A


class AnalysisError(ValueError):
    pass


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScopeEntry:
    qualifier: Optional[str]
    name: str
    ordinal: int
    data_type: T.DataType
    nullable: bool

    def ref(self) -> BoundReference:
        return BoundReference(self.ordinal, self.data_type, self.nullable,
                              ref_name=self.name)


class Scope:
    def __init__(self, entries: Sequence[ScopeEntry]):
        self.entries = list(entries)

    @staticmethod
    def for_plan(plan, qualifier: Optional[str]) -> "Scope":
        return Scope([ScopeEntry(qualifier, f.name, i, f.data_type,
                                 f.nullable)
                      for i, f in enumerate(plan.schema.fields)])

    def concat(self, other: "Scope") -> "Scope":
        off = 1 + max((e.ordinal for e in self.entries), default=-1)
        shifted = [dataclasses.replace(e, ordinal=e.ordinal + off)
                   for e in other.entries]
        return Scope(self.entries + shifted)

    def try_resolve(self, name: str,
                    qualifier: Optional[str]) -> Optional[ScopeEntry]:
        name_l = name.lower()
        hits = [e for e in self.entries
                if e.name.lower() == name_l and
                (qualifier is None or
                 (e.qualifier or "").lower() == qualifier.lower())]
        if not hits:
            return None
        if len(hits) > 1 and qualifier is None:
            # identical entry duplicated across qualifiers is ambiguous
            raise AnalysisError(f"ambiguous column {name}")
        return hits[0]

    def resolve(self, name: str, qualifier: Optional[str]) -> ScopeEntry:
        e = self.try_resolve(name, qualifier)
        if e is None:
            known = ", ".join(
                (f"{e.qualifier}." if e.qualifier else "") + e.name
                for e in self.entries[:25])
            q = f"{qualifier}." if qualifier else ""
            raise AnalysisError(f"cannot resolve column {q}{name}; "
                                f"available: {known}")
        return e


# ---------------------------------------------------------------------------
# function registry
# ---------------------------------------------------------------------------

_AGG_FUNCS = {"sum", "avg", "count", "min", "max", "stddev_samp", "stddev",
              "stddev_pop", "var_samp", "variance", "var_pop", "first",
              "last", "collect_list", "collect_set"}


def _is_agg_call(e: A.SqlExpr) -> bool:
    return isinstance(e, A.FuncCall) and e.name in _AGG_FUNCS and \
        e.window is None


def _contains_agg(e: A.SqlExpr) -> bool:
    if _is_agg_call(e):
        return True
    return any(_contains_agg(c) for c in _ast_children(e))


def _ast_children(e: A.SqlExpr) -> List[A.SqlExpr]:
    out = []
    if isinstance(e, A.Alias):
        out = [e.expr]
    elif isinstance(e, A.FieldAccess):
        out = [e.operand]
    elif isinstance(e, A.BinaryOp):
        out = [e.left, e.right]
    elif isinstance(e, A.UnaryOp):
        out = [e.operand]
    elif isinstance(e, A.IsNull):
        out = [e.operand]
    elif isinstance(e, A.Between):
        out = [e.operand, e.low, e.high]
    elif isinstance(e, A.InList):
        out = [e.operand] + e.values
    elif isinstance(e, A.InSubquery):
        out = [e.operand]
    elif isinstance(e, A.Like):
        out = [e.operand]
    elif isinstance(e, A.FuncCall):
        out = list(e.args)
        if e.window is not None:
            out += e.window.partition_by + [s.expr for s in
                                            e.window.order_by]
    elif isinstance(e, A.Cast):
        out = [e.expr]
    elif isinstance(e, A.Case):
        out = ([e.operand] if e.operand else []) + \
            [x for b in e.branches for x in b] + \
            ([e.otherwise] if e.otherwise else [])
    return out


def _split_disjuncts(e: A.SqlExpr) -> List[A.SqlExpr]:
    if isinstance(e, A.BinaryOp) and e.op == "or":
        return _split_disjuncts(e.left) + _split_disjuncts(e.right)
    return [e]


def _and_all(parts: List[A.SqlExpr]) -> A.SqlExpr:
    out = parts[0]
    for p in parts[1:]:
        out = A.BinaryOp("and", out, p)
    return out


def _or_all(parts: List[A.SqlExpr]) -> A.SqlExpr:
    out = parts[0]
    for p in parts[1:]:
        out = A.BinaryOp("or", out, p)
    return out


def _factor_or_common(e: A.SqlExpr) -> List[A.SqlExpr]:
    """Hoists conjuncts common to EVERY branch of an OR:
    ``(A and X) or (A and Y) -> A and (X or Y)``.

    TPC-DS repeats join equalities inside each demographic OR branch
    (q13/q48 shape); without factoring, the join planner sees no equi
    keys and cross-joins the dimensions (Spark's optimizer performs the
    same extraction before join planning)."""
    branches = _split_disjuncts(e)
    if len(branches) < 2:
        return [e]
    conj_lists = [_split_conjuncts(b) for b in branches]
    common = [c for c in conj_lists[0]
              if all(any(c == c2 for c2 in cl) for cl in conj_lists[1:])]
    if not common:
        return [e]
    rests = []
    for cl in conj_lists:
        rest = [c for c in cl if not any(c == cm for cm in common)]
        if not rest:       # a branch fully covered by the common part:
            return common  # the OR is implied by it
        rests.append(_and_all(rest))
    return common + [_or_all(rests)]


def _split_conjuncts(e: Optional[A.SqlExpr]) -> List[A.SqlExpr]:
    if e is None:
        return []
    if isinstance(e, A.BinaryOp) and e.op == "and":
        return _split_conjuncts(e.left) + _split_conjuncts(e.right)
    if isinstance(e, A.BinaryOp) and e.op == "or":
        factored = _factor_or_common(e)
        if len(factored) > 1 or factored[0] is not e:
            return [c for f in factored for c in _split_conjuncts(f)]
    return [e]


def _count_table_refs(node, name: str, skip=None) -> int:
    """How many times ``name`` is referenced as a table anywhere in the
    statement AST (relations, subqueries, sibling CTE bodies).  ``skip``
    excludes the CTE's own definition.  Shadowing by an inner CTE of the
    same name overcounts — harmless: it only wraps a single-use CTE in a
    cache node."""
    import dataclasses as _dc
    cnt = 0
    stack = [node]
    while stack:
        x = stack.pop()
        if x is skip:
            continue
        if isinstance(x, A.TableRef) and x.name.lower() == name:
            cnt += 1
        if _dc.is_dataclass(x) and not isinstance(x, type):
            for f in _dc.fields(x):
                stack.append(getattr(x, f.name))
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
    return cnt


def _referenced_names(q: A.Select) -> Optional[set]:
    """Lower-case names of the columns a SELECT's text refers to anywhere
    (its subqueries included), or ``None`` where a ``*`` in a select list
    refers to all of them.  Names only: a name two relations share counts
    for both, which errs towards the larger estimate."""
    import dataclasses as _dc
    names: set = set()
    stack: list = [q]
    while stack:
        x = stack.pop()
        if isinstance(x, A.ColumnRef):
            names.add(x.name.lower())
        elif isinstance(x, A.Select) and \
                any(isinstance(p, A.Star) for p in x.projections):
            return None
        elif isinstance(x, A.Join) and x.using:
            names.update(n.lower() for n in x.using)
        if _dc.is_dataclass(x) and not isinstance(x, type):
            stack.extend(getattr(x, f.name) for f in _dc.fields(x))
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
    return names


def _has_subquery(e: A.SqlExpr) -> bool:
    if isinstance(e, (A.InSubquery, A.Exists, A.ScalarSubquery)):
        return True
    return any(_has_subquery(c) for c in _ast_children(e))


def _column_refs(e: A.SqlExpr) -> List[A.ColumnRef]:
    out = []
    if isinstance(e, A.ColumnRef):
        out.append(e)
    for c in _ast_children(e):
        # do not descend into subquery bodies: their refs live in their own
        # scopes
        out.extend(_column_refs(c))
    return out


def _parse_type(name: str) -> T.DataType:
    base = name.split("(")[0]
    args = []
    if "(" in name:
        args = [int(x) for x in name[name.index("(") + 1:-1].split(",")]
    m = {"int": T.INT, "integer": T.INT, "bigint": T.LONG, "long": T.LONG,
         "smallint": T.SHORT, "tinyint": T.BYTE, "float": T.FLOAT,
         "real": T.FLOAT, "double": T.DOUBLE, "string": T.STRING,
         "boolean": T.BOOLEAN, "date": T.DATE, "timestamp": T.TIMESTAMP}
    if base in m:
        return m[base]
    if base in ("decimal", "numeric"):
        p = args[0] if args else 10
        s = args[1] if len(args) > 1 else 0
        return T.DecimalType(p, s)
    if base in ("char", "varchar"):
        return T.STRING
    raise AnalysisError(f"unsupported cast type {name}")


# ---------------------------------------------------------------------------
# analyzer
# ---------------------------------------------------------------------------

class Analyzer:
    def __init__(self, session):
        self.session = session
        #: innermost last: the column names the SELECT being planned refers
        #: to (``None``: all of them), which the join's size rule reads
        self._referenced: List[Optional[set]] = [None]

    # -- public -------------------------------------------------------------
    def plan(self, q: A.Select):
        """Returns a DataFrame for the query."""
        from spark_rapids_tpu.session import DataFrame
        plan, names = self._select(q, cte_env={}, outer=None)
        return DataFrame(plan, self.session)

    # -- relations ----------------------------------------------------------
    def _relation(self, rel: A.Relation, cte_env) -> Tuple[object, Scope]:
        from spark_rapids_tpu.exec import joins as JX
        if isinstance(rel, A.TableRef):
            plan = self._lookup_table(rel.name, cte_env)
            return plan, Scope.for_plan(plan, rel.alias or rel.name)
        if isinstance(rel, A.SubqueryRef):
            plan, names = self._select(rel.query, cte_env, outer=None)
            return plan, Scope.for_plan(plan, rel.alias)
        if isinstance(rel, A.Join):
            lplan, lscope = self._relation(rel.left, cte_env)
            rplan, rscope = self._relation(rel.right, cte_env)
            scope = lscope.concat(rscope)
            if rel.kind == "cross":
                plan = self._join(lplan, rplan, [], [], "cross", None)
                return plan, scope
            if rel.using:
                lkeys = [lscope.resolve(n, None).ref() for n in rel.using]
                rkeys = [rscope.resolve(n, None).ref() for n in rel.using]
                plan = self._join(lplan, rplan, lkeys, rkeys, rel.kind,
                                  None)
                return plan, scope
            # ON condition: extract equi pairs left vs right
            conjs = _split_conjuncts(rel.condition)
            lkeys, rkeys, residual = [], [], []
            nl = len(lplan.schema.fields)
            for c in conjs:
                pair = self._equi_pair(c, lscope, rscope)
                if pair is not None:
                    lkeys.append(pair[0])
                    rkeys.append(pair[1])
                else:
                    residual.append(c)
            cond = None
            if residual:
                cond = self._conj_expr(residual, scope)
            plan = self._join(lplan, rplan, lkeys, rkeys, rel.kind, cond)
            return plan, scope
        raise AnalysisError(f"unsupported relation {rel}")

    def _lookup_table(self, name: str, cte_env):
        key = name.lower()
        if key in cte_env:
            entry = cte_env[key]
            if entry["plan"] is None:
                plan, _ = self._select(entry["ast"], entry["env"],
                                       outer=None)
                from spark_rapids_tpu import config as C
                if entry.get("multi") and \
                        self.session.conf.get(C.CTE_REUSE_ENABLED.key):
                    # referenced more than once: materialize once and
                    # share (the q4/q11 year_total CTE would otherwise
                    # execute per reference)
                    from spark_rapids_tpu.exec.basic import CpuCteCacheExec
                    plan = CpuCteCacheExec(plan)
                entry["plan"] = plan
            return entry["plan"]
        df = self.session.catalog_lookup(name)
        if df is None:
            raise AnalysisError(f"table or view not found: {name}")
        return df._plan

    def _equi_pair(self, c: A.SqlExpr, lscope: Scope, rscope: Scope):
        """cond is `x = y` with x fully in lscope and y in rscope (either
        order) -> (left_expr, right_expr) or None."""
        if not (isinstance(c, A.BinaryOp) and c.op == "="):
            return None
        if _has_subquery(c):
            return None
        for a, b in ((c.left, c.right), (c.right, c.left)):
            try:
                ae = self._expr(a, lscope)
                be = self._expr(b, rscope)
            except AnalysisError:
                continue
            # the other side must NOT also resolve on the same scope (e.g.
            # t1.x = t1.y is a filter, not a join edge)
            if self._resolves(a, rscope) or self._resolves(b, lscope):
                continue
            ae, be = self._coerce_pair(ae, be)
            return ae, be
        return None

    def _resolves(self, e: A.SqlExpr, scope: Scope) -> bool:
        try:
            self._expr(e, scope)
            return True
        except AnalysisError:
            return False

    def _coerce_pair(self, a: Expression, b: Expression):
        if str(a.data_type) == str(b.data_type):
            return a, b
        ta, tb = a.data_type, b.data_type
        rank = {"byte": 0, "short": 1, "int": 2, "long": 3, "float": 4,
                "double": 5}
        na, nb = rank.get(ta.simple_name), rank.get(tb.simple_name)
        if na is not None and nb is not None:
            if na < nb:
                return Cast(a, tb), b
            return a, Cast(b, ta)
        if isinstance(ta, T.DecimalType) or isinstance(tb, T.DecimalType):
            return Cast(a, T.DOUBLE), Cast(b, T.DOUBLE)
        return a, Cast(b, ta)

    def _join(self, lplan, rplan, lkeys, rkeys, kind, cond):
        from spark_rapids_tpu.exec import joins as JX
        import spark_rapids_tpu.ops.join_ops as J
        how = {"inner": J.INNER, "left": J.LEFT_OUTER,
               "right": J.RIGHT_OUTER, "full": J.FULL_OUTER,
               "cross": J.CROSS, "semi": J.LEFT_SEMI,
               "anti": J.LEFT_ANTI}[kind]
        if not lkeys:
            if how in (J.RIGHT_OUTER, J.FULL_OUTER):
                raise AnalysisError(
                    f"{kind} join requires at least one equality condition")
            return JX.CpuBroadcastNestedLoopJoinExec([], [], how, cond,
                                                     lplan, rplan)
        # decompose struct-constructor pairs BEFORE the join is planned:
        # both sides must shuffle by the same field keys the join will
        # probe with
        lkeys, rkeys, nsafe = JX.expand_struct_key_pairs(lkeys, rkeys)
        from spark_rapids_tpu.plan.join_selection import plan_equi_join
        return plan_equi_join(self.session, lplan, rplan, lkeys, rkeys, how,
                              cond, nsafe, referenced=self._referenced[-1])

    # -- select core --------------------------------------------------------
    def _select(self, q: A.Select, cte_env, outer: Optional[Scope]):
        """Returns (plan, output_names)."""
        self._referenced.append(_referenced_names(q))
        try:
            return self._select_core(q, cte_env, outer)
        finally:
            self._referenced.pop()

    def _select_core(self, q: A.Select, cte_env, outer: Optional[Scope]):
        from spark_rapids_tpu.exec.basic import (CpuFilterExec,
                                                 CpuProjectExec)
        env = dict(cte_env)
        for name, sub in q.ctes:
            env[name.lower()] = {"ast": sub, "env": dict(env), "plan": None,
                                 "multi": _count_table_refs(q, name.lower(),
                                                            skip=sub) > 1}

        if not q.relations:
            plan = self._values_plan(q)
            scope = Scope.for_plan(plan, None)
            names = [f.name for f in plan.schema.fields]
            return self._finish(q, plan, scope, env, names)

        rels = [self._relation(r, env) for r in q.relations]
        plan, scope, residual = self._join_graph(rels,
                                                 _split_conjuncts(q.where))

        # residual predicates: subquery machinery + plain filters
        n_base_cols = len(plan.schema.fields)
        preds: List[Expression] = []
        for c in residual:
            plan, pred = self._predicate_with_subqueries(c, plan, scope,
                                                         env, outer)
            if pred is not None:
                preds.append(pred)
        if preds:
            p = preds[0]
            for x in preds[1:]:
                p = PR.And(p, x)
            plan = CpuFilterExec(p, plan)
        if len(plan.schema.fields) > n_base_cols:
            # drop columns appended by subquery joins
            keep = []
            for i in range(n_base_cols):
                f = plan.schema.fields[i]
                keep.append(Alias(BoundReference(i, f.data_type, f.nullable),
                                  f.name))
            plan = CpuProjectExec(keep, plan)

        names = None
        return self._finish(q, plan, scope, env, names)

    def _join_graph(self, rels, conjuncts: List[A.SqlExpr]):
        """Builds a join tree from FROM items + WHERE conjuncts: single-
        table predicates push below the joins, equality conjuncts spanning
        two relations become join keys (greedy connection order), anything
        else (incl. subquery conjuncts) is returned as residual.
        Catalyst analog: PushPredicateThroughJoin + ReorderJoin."""
        from spark_rapids_tpu.exec.basic import CpuFilterExec
        pushed: Dict[int, List[A.SqlExpr]] = {}
        residual: List[A.SqlExpr] = []
        edges: List[A.SqlExpr] = []
        for c in conjuncts:
            if _has_subquery(c):
                residual.append(c)
                continue
            owners = [i for i, (_p, s) in enumerate(rels)
                      if self._resolves(c, s)]
            if len(owners) > 1 and _column_refs(c):
                # Spark raises AMBIGUOUS_REFERENCE here; silently filtering
                # only the first relation would produce wrong results
                refs = ", ".join(r.name for r in _column_refs(c))
                raise AnalysisError(
                    f"ambiguous column reference in predicate "
                    f"{c!r} (columns [{refs}] resolve in "
                    f"{len(owners)} FROM relations); qualify the columns")
            if owners:
                pushed.setdefault(owners[0], []).append(c)
                continue
            is_edge = isinstance(c, A.BinaryOp) and c.op == "="
            (edges if is_edge else residual).append(c)

        rels2 = []
        for i, (plan, scope) in enumerate(rels):
            for c in pushed.get(i, []):
                plan = CpuFilterExec(self._expr(c, scope), plan)
            rels2.append((plan, scope))

        # greedy join-graph: start at the first relation, repeatedly attach
        # a relation connected by an equi edge; cross join as a last resort
        plan, scope = rels2[0]
        joined = {0}
        remaining_edges = list(edges)
        while len(joined) < len(rels2):
            best = None
            for j in range(len(rels2)):
                if j in joined:
                    continue
                jplan, jscope = rels2[j]
                lkeys, rkeys, used = [], [], []
                for c in remaining_edges:
                    pair = self._equi_pair(c, scope, jscope)
                    if pair is not None:
                        lkeys.append(pair[0])
                        rkeys.append(pair[1])
                        used.append(c)
                if lkeys:
                    best = (j, lkeys, rkeys, used)
                    break
            if best is None:
                j = next(k for k in range(len(rels2)) if k not in joined)
                jplan, jscope = rels2[j]
                plan = self._join(plan, jplan, [], [], "cross", None)
                scope = scope.concat(jscope)
                joined.add(j)
                continue
            j, lkeys, rkeys, used = best
            jplan, jscope = rels2[j]
            plan = self._join(plan, jplan, lkeys, rkeys, "inner", None)
            scope = scope.concat(jscope)
            joined.add(j)
            for c in used:
                remaining_edges.remove(c)
        residual.extend(remaining_edges)
        return plan, scope, residual

    def _values_plan(self, q: A.Select):
        """SELECT without FROM: single-row projection."""
        from spark_rapids_tpu.exec.basic import CpuProjectExec, CpuRangeExec
        base = CpuRangeExec(0, 1, 1, 1)
        scope = Scope([])
        exprs = []
        for i, p in enumerate(q.projections):
            name = p.name if isinstance(p, A.Alias) else f"col{i}"
            body = p.expr if isinstance(p, A.Alias) else p
            exprs.append(Alias(self._expr(body, scope), name))
        return CpuProjectExec(exprs, base)

    # -- aggregation / projection / tail ------------------------------------
    def _finish(self, q: A.Select, plan, scope: Scope, env, names_hint):
        from spark_rapids_tpu.exec.basic import (CpuFilterExec,
                                                 CpuProjectExec)
        from spark_rapids_tpu.session import DataFrame, GroupedData

        has_agg = any(_contains_agg(p) for p in q.projections) or \
            (q.having is not None and _contains_agg(q.having)) or \
            q.group_by is not None

        # expand stars
        projections: List[A.SqlExpr] = []
        for p in q.projections:
            if isinstance(p, A.Star):
                for e in scope.entries:
                    if p.qualifier is None or \
                            (e.qualifier or "").lower() == \
                            p.qualifier.lower():
                        projections.append(
                            A.Alias(A.ColumnRef(e.name, e.qualifier),
                                    e.name))
                if not projections:
                    raise AnalysisError(f"star {p} expanded to nothing")
            else:
                projections.append(p)

        out_names = []
        for i, p in enumerate(projections):
            if isinstance(p, A.Alias):
                out_names.append(p.name)
            elif isinstance(p, A.ColumnRef):
                out_names.append(p.name)
            else:
                out_names.append(f"col{i}")

        order_items = list(q.order_by)

        if has_agg:
            plan, out_exprs, order_items = self._plan_aggregate(
                q, projections, plan, scope, env, order_items)
        else:
            out_exprs = []
            for p, nm in zip(projections, out_names):
                body = p.expr if isinstance(p, A.Alias) else p
                out_exprs.append(Alias(self._expr_sq(body, plan, scope,
                                                     env), nm))
            # window functions ride the DataFrame planner
            df = DataFrame(plan, self.session)
            wplan, bound = df._plan_windows(out_exprs)
            plan = CpuProjectExec(bound, wplan)

        out_scope = Scope([ScopeEntry(None, nm, i, f.data_type, f.nullable)
                           for i, (nm, f) in enumerate(
                               zip(out_names, plan.schema.fields))])

        if q.distinct:
            df = DataFrame(plan, self.session)
            plan = df.distinct()._plan

        # INTERSECT binds tighter than UNION/EXCEPT (SQL standard; Spark/
        # Catalyst precedence): group each INTERSECT with its preceding
        # term first, then fold UNION/EXCEPT left-to-right
        groups = [(None, plan)]
        for op, rhs in q.set_ops:
            rplan, _ = self._select(rhs, env, outer=None)
            if op == "intersect":
                prev_op, prev = groups[-1]
                merged = DataFrame(prev, self.session).intersect(
                    DataFrame(rplan, self.session))._plan
                groups[-1] = (prev_op, merged)
            else:
                groups.append((op, rplan))
        plan = groups[0][1]
        for op, rplan in groups[1:]:
            df = DataFrame(plan, self.session)
            rdf = DataFrame(rplan, self.session)
            if op == "union all":
                plan = df.union(rdf)._plan
            elif op == "union":
                plan = df.union(rdf).distinct()._plan
            else:
                plan = df.except_distinct(rdf)._plan

        if order_items:
            plan = self._order(plan, out_scope, order_items, out_names)
        if q.limit is not None:
            df = DataFrame(plan, self.session)
            plan = df.limit(q.limit)._plan
        return plan, out_names

    def _order(self, plan, out_scope: Scope, items: List[A.SortItem],
               out_names: List[str]):
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.sort import CpuSortExec, SortSpec
        from spark_rapids_tpu.plan.partitioning import RangePartitioning
        specs = []
        for it in items:
            e = it.expr
            if isinstance(e, A.Literal) and isinstance(e.value, int) and \
                    not isinstance(e.value, bool):
                idx = e.value - 1
                if not (0 <= idx < len(out_names)):
                    raise AnalysisError(f"ORDER BY ordinal {e.value} out of "
                                        "range")
                f = plan.schema.fields[idx]
                bound = BoundReference(idx, f.data_type, f.nullable)
            else:
                try:
                    bound = self._expr(e, out_scope)
                except AnalysisError:
                    # ORDER BY tbl.col where the output column carries the
                    # bare name (SQL permits ordering by input columns that
                    # survive the projection)
                    if isinstance(e, A.ColumnRef) and e.qualifier:
                        bound = self._expr(A.ColumnRef(e.name), out_scope)
                    else:
                        raise
            specs.append(SortSpec(bound, it.ascending, it.nulls_first))
        n = plan.num_partitions
        if n > 1:
            part = RangePartitioning(specs, n)
            plan = CpuShuffleExchangeExec(part, plan,
                                          shuffle_env=self.session.shuffle_env)
        return CpuSortExec(specs, plan, global_sort=True)

    # -- aggregate planning --------------------------------------------------
    def _plan_aggregate(self, q: A.Select, projections, plan, scope, env,
                        order_items):
        from spark_rapids_tpu.exec.basic import (CpuFilterExec,
                                                 CpuProjectExec)
        from spark_rapids_tpu.session import DataFrame, GroupedData

        group_exprs = list(q.group_by.exprs) if q.group_by else []
        rollup = bool(q.group_by and q.group_by.rollup)
        cube = bool(q.group_by and q.group_by.cube)

        # collect aggregate calls from projections + having + order by
        agg_calls: List[A.FuncCall] = []

        def collect(e):
            if _is_agg_call(e):
                if e not in agg_calls:
                    agg_calls.append(e)
                return
            for c in _ast_children(e):
                collect(c)

        for p in projections:
            collect(p)
        if q.having is not None:
            collect(q.having)
        for it in order_items:
            collect(it.expr)

        def _has_grouping_call(e) -> bool:
            if isinstance(e, A.FuncCall) and e.name == "grouping":
                return True
            return any(_has_grouping_call(c) for c in _ast_children(e))

        need_gid = (any(_has_grouping_call(p) for p in projections) or
                    (q.having is not None and
                     _has_grouping_call(q.having)) or
                    any(_has_grouping_call(it.expr) for it in order_items))

        key_bound = [self._expr_sq(g, plan, scope, env)
                     for g in group_exprs]
        # struct-constructor grouping keys decompose into their field
        # exprs (struct equality/grouping is field-wise; no device struct
        # plane needed — Spark's RemoveRedundantAliases-era rewrite
        # family).  key_map: original ki -> (start, width, struct|None)
        from spark_rapids_tpu.expressions.collections import \
            CreateNamedStruct as _CNS
        key_map = []
        if not (rollup or cube):
            expanded = []
            for k in key_bound:
                if isinstance(k, _CNS):
                    key_map.append((len(expanded), len(k.children), k))
                    expanded.extend(k.children)
                else:
                    key_map.append((len(expanded), 1, None))
                    expanded.append(k)
            key_bound = expanded
        else:
            key_map = [(i, 1, None) for i in range(len(key_bound))]
        agg_exprs = []
        for i, call in enumerate(agg_calls):
            agg_exprs.append(Alias(self._agg_func(call, plan, scope, env),
                                   f"_agg{i}"))

        df = DataFrame(plan, self.session)
        key_names = [f"_key{i}" for i in range(len(key_bound))]
        gd = GroupedData(df, [Alias(k, n) for k, n in
                              zip(key_bound, key_names)])
        if rollup or cube:
            sets = []
            n = len(key_bound)
            if rollup:
                sets = [key_names[:k] for k in range(n, -1, -1)]
            else:
                import itertools
                sets = [list(c) for r in range(n, -1, -1)
                        for c in itertools.combinations(key_names, r)]
            name_to_idx = {n_: i for i, n_ in enumerate(key_names)}
            gd = GroupedData(df, [Alias(k, n_) for k, n_ in
                                  zip(key_bound, key_names)],
                             grouping_sets=[tuple(sorted(
                                 name_to_idx[x] for x in s)) for s in sets],
                             key_names=key_names)
            gd._keep_gid = need_gid
        elif need_gid:
            raise AnalysisError(
                "grouping() requires ROLLUP/CUBE/GROUPING SETS")
        agg_df = gd.agg(*agg_exprs)
        aplan = agg_df._plan

        # scope over agg output: keys (by structural AST match) + agg slots
        agg_schema = aplan.schema

        def _key_ref(ki: int) -> Expression:
            start, width, st = key_map[ki]
            if st is None:
                f = agg_schema.fields[start]
                return BoundReference(start, f.data_type, f.nullable)
            # struct key: reassemble from its decomposed field columns
            refs = [BoundReference(start + i,
                                   agg_schema.fields[start + i].data_type,
                                   agg_schema.fields[start + i].nullable)
                    for i in range(width)]
            return _CNS(st.names, refs)

        def rewrite(e: A.SqlExpr) -> Expression:
            # grouping key? structural match against group_exprs
            for ki, g in enumerate(group_exprs):
                if e == g:
                    return _key_ref(ki)
            if _is_agg_call(e):
                ai = agg_calls.index(e)
                idx = len(key_bound) + ai
                f = agg_schema.fields[idx]
                return BoundReference(idx, f.data_type, f.nullable)
            g = _grouping_bit(e)
            if g is not None:
                return g
            return self._expr_generic(e, rewrite_leaf, None)

        def _grouping_bit(e) -> Optional[Expression]:
            """grouping(col) = bit of __grouping_id (appended last by
            _agg_grouping_sets when _keep_gid): 1 when col is aggregated
            away in this grouping set (Spark semantics)."""
            if not (isinstance(e, A.FuncCall) and e.name == "grouping"):
                return None
            arg = e.args[0]
            ki = next((i for i, g in enumerate(group_exprs)
                       if arg == g), None)
            if ki is None:
                raise AnalysisError(
                    f"grouping() argument {arg} is not a grouping column")
            gid_idx = len(agg_schema.fields) - 1
            gidref = BoundReference(gid_idx, T.LONG, False)
            bit = len(group_exprs) - 1 - ki
            return AR.Remainder(
                AR.IntegralDivide(gidref, Literal(1 << bit, T.LONG)),
                Literal(2, T.LONG))

        def rewrite_leaf(e: A.SqlExpr) -> Optional[Expression]:
            for ki, g in enumerate(group_exprs):
                if e == g:
                    return _key_ref(ki)
            gb = _grouping_bit(e)
            if gb is not None:
                return gb
            if isinstance(e, A.ScalarSubquery):
                # uncorrelated scalar in HAVING / post-agg projections
                # (q23/q24/q44): evaluate eagerly, inline as literal
                from spark_rapids_tpu.session import DataFrame
                p_, _ = self._select(e.query, env, outer=None)
                rows = DataFrame(p_, self.session).collect()
                if not rows:
                    return lit(None)
                return lit(rows[0][list(rows[0].keys())[0]])
            if _is_agg_call(e):
                ai = agg_calls.index(e)
                idx = len(key_bound) + ai
                f = agg_schema.fields[idx]
                return BoundReference(idx, f.data_type, f.nullable)
            if isinstance(e, A.ColumnRef):
                # a bare column in projections must be a grouping column
                for ki, g in enumerate(group_exprs):
                    if isinstance(g, A.ColumnRef) and \
                            g.name.lower() == e.name.lower() and \
                            (e.qualifier is None or g.qualifier is None or
                             g.qualifier.lower() == e.qualifier.lower()):
                        return _key_ref(ki)
                raise AnalysisError(
                    f"column {e.name} is neither grouped nor aggregated")
            return None

        out_exprs = []
        for i, p in enumerate(projections):
            nm = p.name if isinstance(p, A.Alias) else (
                p.name if isinstance(p, A.ColumnRef) else f"col{i}")
            body = p.expr if isinstance(p, A.Alias) else p
            out_exprs.append(Alias(rewrite(body), nm))

        plan = aplan
        if q.having is not None:
            plan = CpuFilterExec(rewrite(q.having), plan)

        # ORDER BY over aggregates: rewrite into hidden columns
        new_order = []
        hidden = []
        for it in order_items:
            e = it.expr
            if isinstance(e, A.Literal) and isinstance(e.value, int) and \
                    not isinstance(e.value, bool):
                new_order.append(it)
                continue
            # try as output alias first (resolved later)
            if isinstance(e, A.ColumnRef) and e.qualifier is None and \
                    any((p.name if isinstance(p, A.Alias) else "") ==
                        e.name for p in projections):
                new_order.append(it)
                continue
            try:
                bound = rewrite(e)
            except AnalysisError:
                new_order.append(it)
                continue
            hname = f"_ord{len(hidden)}"
            hidden.append(Alias(bound, hname))
            new_order.append(A.SortItem(A.ColumnRef(hname), it.ascending,
                                        it.nulls_first))

        # windows over aggregate output (q36's rank() over grouped sums):
        # extract WindowExpressions, insert the window exec over the agg
        # plan, and rebind the projections to its appended columns
        from spark_rapids_tpu.expressions.window_exprs import \
            WindowExpression as _WExpr
        if any(e.collect(lambda x: isinstance(x, _WExpr))
               for e in out_exprs + hidden):
            wdf = DataFrame(plan, self.session)
            plan, rebound = wdf._plan_windows(out_exprs + hidden)
            out_exprs = rebound[:len(out_exprs)]
            hidden = rebound[len(out_exprs):]

        proj = out_exprs + hidden
        plan = CpuProjectExec(proj, plan)
        if hidden:
            # sort on hidden columns, then drop them
            out_scope = Scope([ScopeEntry(None, a.alias_name, i,
                                          a.data_type, a.nullable)
                               for i, a in enumerate(proj)])
            plan = self._order(plan, out_scope, new_order,
                               [a.alias_name for a in proj])
            keep = []
            for i in range(len(out_exprs)):
                f = plan.schema.fields[i]
                keep.append(Alias(BoundReference(i, f.data_type,
                                                 f.nullable), f.name))
            plan = CpuProjectExec(keep, plan)
            new_order = []
        return plan, out_exprs, new_order

    def _agg_func(self, call: A.FuncCall, plan, scope, env) -> Expression:
        if call.distinct:
            if call.name == "count" and len(call.args) == 1 and \
                    not call.star:
                return AG.CountDistinct(
                    self._expr_sq(call.args[0], plan, scope, env))
            raise AnalysisError(
                f"{call.name}(DISTINCT ...) not supported yet "
                "(count(DISTINCT col) is)")
        if call.star or not call.args:
            if call.name != "count":
                raise AnalysisError(f"{call.name}(*) is not valid")
            return AG.Count(lit(1))
        arg = self._expr_sq(call.args[0], plan, scope, env)
        m = {"sum": AG.Sum, "avg": AG.Average, "count": AG.Count,
             "min": AG.Min, "max": AG.Max,
             "stddev_samp": AG.StddevSamp, "stddev": AG.StddevSamp,
             "stddev_pop": AG.StddevPop, "var_samp": AG.VarianceSamp,
             "variance": AG.VarianceSamp, "var_pop": AG.VariancePop,
             "collect_list": AG.CollectList, "collect_set": AG.CollectSet}
        if call.name in ("first", "last"):
            cls = AG.First if call.name == "first" else AG.Last
            return cls(arg)
        if call.name not in m:
            raise AnalysisError(f"unknown aggregate {call.name}")
        return m[call.name](arg)

    # -- subquery machinery ---------------------------------------------------
    def _predicate_with_subqueries(self, c: A.SqlExpr, plan, scope: Scope,
                                   env, outer):
        """Lowers subqueries inside conjunct ``c``; returns (new_plan,
        bound predicate or None when fully consumed by a semi/anti join)."""
        import spark_rapids_tpu.ops.join_ops as J
        # top-level [NOT] EXISTS / [NOT] IN: semi/anti join, no marker col
        node = c
        negated = False
        if isinstance(node, A.UnaryOp) and node.op == "not":
            negated = True
            node = node.operand
        if isinstance(node, A.Exists):
            plan = self._exists_join(
                node.query, plan, scope, env,
                anti=negated != node.negated, marker=None)
            return plan, None
        if isinstance(node, A.InSubquery) and not _has_subquery(node.operand):
            plan = self._in_join(node, plan, scope, env,
                                 anti=negated != node.negated, marker=None)
            return plan, None

        # general case: replace each subquery node with a marker/scalar col
        state = {"plan": plan}

        def lower(e: A.SqlExpr) -> Optional[Expression]:
            if isinstance(e, A.ScalarSubquery):
                val = self._scalar_subquery(e.query, state, scope, env)
                return val
            if isinstance(e, A.Exists):
                marker = self._next_marker()
                state["plan"] = self._exists_join(
                    e.query, state["plan"], scope, env, anti=False,
                    marker=marker)
                idx = len(state["plan"].schema.fields) - 1
                ref = BoundReference(idx, T.BOOLEAN, True)
                out = PR.IsNotNull(ref)
                return PR.Not(out) if e.negated else out
            if isinstance(e, A.InSubquery):
                marker = self._next_marker()
                state["plan"] = self._in_join(
                    e, state["plan"], scope, env, anti=False, marker=marker)
                idx = len(state["plan"].schema.fields) - 1
                ref = BoundReference(idx, T.BOOLEAN, True)
                out = PR.IsNotNull(ref)
                return PR.Not(out) if e.negated else out
            return None

        bound = self._expr_generic(c, lower, scope)
        return state["plan"], bound

    def _next_marker(self) -> str:
        # Per-instance (one Analyzer per sql() call): re-parsing the same
        # SQL must yield the same marker names, or the serving layer's
        # normalized plan signatures differ across parses and identical
        # queries miss the plan cache.  Markers only disambiguate
        # subqueries WITHIN one query — they bind positionally and the
        # final projection drops them, so cross-parse uniqueness is not
        # needed.
        self._marker_n = getattr(self, "_marker_n", 0) + 1
        return f"_exists{self._marker_n}"

    def _correlation_split(self, sub: A.Select, inner_scope: Scope,
                           outer_scope: Scope):
        """Splits sub.where into (correlated equality pairs, inner
        conjuncts).  A correlated pair is (outer_expr_ast, inner_expr_ast).
        """
        pairs = []
        inner = []
        for c in _split_conjuncts(sub.where):
            if isinstance(c, A.BinaryOp) and c.op == "=" and \
                    not _has_subquery(c):
                sides = []
                for e in (c.left, c.right):
                    in_inner = self._resolves(e, inner_scope)
                    in_outer = self._resolves(e, outer_scope)
                    sides.append((e, in_inner, in_outer))
                (le, li, lo), (re_, ri, ro) = sides
                # the inner side may ALSO resolve in the outer scope (a
                # bare column name shared by both relations, q41's
                # i_manufact = i1.i_manufact): innermost scope wins per
                # SQL scoping, so only the outer side must be strictly
                # outer-only
                if li and ro and not ri:
                    pairs.append((re_, le))
                    continue
                if ri and lo and not li:
                    pairs.append((le, re_))
                    continue
            inner.append(c)
        return pairs, inner

    def _exists_join(self, sub: A.Select, plan, scope: Scope, env,
                     anti: bool, marker: Optional[str]):
        """[NOT] EXISTS lowering.  marker=None -> semi/anti join;
        marker=name -> LEFT join appending a nullable marker column."""
        from spark_rapids_tpu.exec.basic import CpuFilterExec, CpuProjectExec
        import spark_rapids_tpu.ops.join_ops as J
        # build the inner FROM + scope (join graph over inner conjuncts)
        rels, naive_scope = self._subquery_parts(sub, env)
        pairs, inner_conj = self._correlation_split(sub, naive_scope, scope)
        if not pairs:
            raise AnalysisError(
                "EXISTS subquery without equality correlation is not "
                "supported")
        inner_plan, inner_scope, leftover = self._join_graph(rels,
                                                             inner_conj)
        for c in leftover:
            inner_plan = CpuFilterExec(self._expr(c, inner_scope),
                                       inner_plan)
        okeys = []
        ikeys = []
        for oe, ie in pairs:
            ok = self._expr(oe, scope)
            ik = self._expr(ie, inner_scope)
            ok, ik = self._coerce_pair(ok, ik)
            okeys.append(ok)
            ikeys.append(ik)
        if marker is None:
            kind = "anti" if anti else "semi"
            return self._join(plan, inner_plan, okeys, ikeys, kind, None)
        # existence marker: distinct inner keys + TRUE, LEFT join
        from spark_rapids_tpu.session import DataFrame
        key_proj = [Alias(k, f"_k{i}") for i, k in enumerate(ikeys)]
        inner_plan = CpuProjectExec(key_proj, inner_plan)
        inner_df = DataFrame(inner_plan, self.session).distinct()
        inner_plan = CpuProjectExec(
            [Alias(BoundReference(i, k.data_type, True), f"_k{i}")
             for i, k in enumerate(ikeys)] +
            [Alias(lit(True), marker)], inner_df._plan)
        new_ikeys = [BoundReference(i, k.data_type, True)
                     for i, k in enumerate(ikeys)]
        joined = self._join(plan, inner_plan, okeys, new_ikeys, "left",
                            None)
        # keep base cols + marker only (drop the _k key columns)
        n_base = len(plan.schema.fields)
        keep = []
        for i in range(n_base):
            f = joined.schema.fields[i]
            keep.append(Alias(BoundReference(i, f.data_type, f.nullable),
                              f.name))
        mf = joined.schema.fields[n_base + len(ikeys)]
        keep.append(Alias(BoundReference(n_base + len(ikeys), mf.data_type,
                                         True), marker))
        return CpuProjectExec(keep, joined)

    def _in_join(self, node: A.InSubquery, plan, scope: Scope, env,
                 anti: bool, marker: Optional[str]):
        """[NOT] IN (subquery): operand = subquery's single output column
        joins like an extra correlation pair."""
        from spark_rapids_tpu.exec.basic import CpuFilterExec, CpuProjectExec
        rels, naive_scope = self._subquery_parts(node.query, env)
        pairs, inner_conj = self._correlation_split(node.query, naive_scope,
                                                    scope)
        inner_plan, inner_scope, leftover = self._join_graph(rels,
                                                             inner_conj)
        for c in leftover:
            inner_plan = CpuFilterExec(self._expr(c, inner_scope),
                                       inner_plan)
        # the subquery's projection provides the IN value column
        projs = node.query.projections
        if len(projs) != 1:
            raise AnalysisError("IN subquery must produce one column")
        body = projs[0].expr if isinstance(projs[0], A.Alias) else projs[0]
        if _contains_agg(body) or node.query.group_by is not None:
            # materialize the aggregate subquery as a plan first
            sub_plan, _ = self._select(node.query, env, outer=None)
            inner_plan = sub_plan
            f = sub_plan.schema.fields[0]
            ival = BoundReference(0, f.data_type, f.nullable)
            pairs = []
        else:
            ival = self._expr(body, inner_scope)
        oval = self._expr(node.operand, scope)
        oval, ival = self._coerce_pair(oval, ival)
        okeys = [oval]
        ikeys = [ival]
        for oe, ie in pairs:
            ok = self._expr(oe, scope)
            ik = self._expr(ie, inner_scope)
            ok, ik = self._coerce_pair(ok, ik)
            okeys.append(ok)
            ikeys.append(ik)
        if marker is None:
            kind = "anti" if anti else "semi"
            return self._join(plan, inner_plan, okeys, ikeys, kind, None)
        from spark_rapids_tpu.session import DataFrame
        key_proj = [Alias(k, f"_k{i}") for i, k in enumerate(ikeys)]
        inner_plan = CpuProjectExec(key_proj, inner_plan)
        inner_df = DataFrame(inner_plan, self.session).distinct()
        inner_plan = CpuProjectExec(
            [Alias(BoundReference(i, k.data_type, True), f"_k{i}")
             for i, k in enumerate(ikeys)] +
            [Alias(lit(True), marker)], inner_df._plan)
        new_ikeys = [BoundReference(i, k.data_type, True)
                     for i, k in enumerate(ikeys)]
        joined = self._join(plan, inner_plan, okeys, new_ikeys, "left",
                            None)
        n_base = len(plan.schema.fields)
        keep = []
        for i in range(n_base):
            f = joined.schema.fields[i]
            keep.append(Alias(BoundReference(i, f.data_type, f.nullable),
                              f.name))
        mf = joined.schema.fields[n_base + len(ikeys)]
        keep.append(Alias(BoundReference(n_base + len(ikeys), mf.data_type,
                                         True), marker))
        return CpuProjectExec(keep, joined)

    def _subquery_parts(self, sub: A.Select, env):
        """Relations of a subquery + the naive concatenated scope (used
        only for resolvability tests; the join graph decides real
        ordinals)."""
        rels = [self._relation(r, env) for r in sub.relations]
        naive = rels[0][1]
        for _p, s in rels[1:]:
            naive = naive.concat(s)
        return rels, naive

    def _scalar_subquery(self, sub: A.Select, state, outer_scope: Scope,
                         env) -> Expression:
        """Scalar subquery -> literal (uncorrelated) or decorrelated join
        column (correlated aggregate)."""
        from spark_rapids_tpu.exec.basic import CpuFilterExec, CpuProjectExec
        _rels, naive_scope = self._subquery_parts(sub, env)
        pairs, inner_conj = self._correlation_split(sub, naive_scope,
                                                    outer_scope)
        if not pairs:
            # uncorrelated: execute eagerly, inline as literal
            from spark_rapids_tpu.session import DataFrame
            plan, _ = self._select(sub, env, outer=None)
            rows = DataFrame(plan, self.session).collect()
            if not rows:
                return lit(None)
            first_key = list(rows[0].keys())[0]
            return lit(rows[0][first_key])
        # correlated aggregate: rebuild as grouped aggregate over the
        # correlation keys, LEFT join onto the outer plan
        if len(sub.projections) != 1:
            raise AnalysisError("correlated scalar subquery must produce "
                                "one column")
        body = sub.projections[0]
        body = body.expr if isinstance(body, A.Alias) else body
        if not _contains_agg(body):
            raise AnalysisError("correlated scalar subquery must be an "
                                "aggregate")
        corr_sub = A.Select(
            projections=[A.Alias(ie, f"_ck{i}")
                         for i, (_oe, ie) in enumerate(pairs)] +
            [A.Alias(body, "_sval")],
            relations=sub.relations,
            where=self._conj_ast(inner_conj),
            group_by=A.GroupingSpec([ie for _oe, ie in pairs]),
            ctes=sub.ctes)
        sub_plan, _ = self._select(corr_sub, env, outer=None)
        okeys = [self._expr(oe, outer_scope) for oe, _ie in pairs]
        nkeys = len(pairs)
        ikeys = []
        for i, ok in enumerate(okeys):
            f = sub_plan.schema.fields[i]
            ik = BoundReference(i, f.data_type, f.nullable)
            ok, ik = self._coerce_pair(ok, ik)
            okeys[i] = ok
            ikeys.append(ik)
        joined = self._join(state["plan"], sub_plan, okeys, ikeys, "left",
                            None)
        n_base = len(state["plan"].schema.fields)
        # keep base + value column
        keep = []
        for i in range(n_base):
            f = joined.schema.fields[i]
            keep.append(Alias(BoundReference(i, f.data_type, f.nullable),
                              f.name))
        vf = joined.schema.fields[n_base + nkeys]
        vname = f"_sq{self._next_marker()}"
        keep.append(Alias(BoundReference(n_base + nkeys, vf.data_type,
                                         True), vname))
        state["plan"] = CpuProjectExec(keep, joined)
        idx = len(state["plan"].schema.fields) - 1
        return BoundReference(idx, vf.data_type, True)

    def _conj_ast(self, conjs: List[A.SqlExpr]) -> Optional[A.SqlExpr]:
        if not conjs:
            return None
        e = conjs[0]
        for c in conjs[1:]:
            e = A.BinaryOp("and", e, c)
        return e

    def _conj_expr(self, conjs: List[A.SqlExpr], scope: Scope) -> Expression:
        e = self._expr(conjs[0], scope)
        for c in conjs[1:]:
            e = PR.And(e, self._expr(c, scope))
        return e

    # -- expression translation ----------------------------------------------
    def _expr(self, e: A.SqlExpr, scope: Scope) -> Expression:
        from spark_rapids_tpu.expressions.base import fold_constants
        return fold_constants(self._expr_generic(e, None, scope))

    def _expr_sq(self, e: A.SqlExpr, plan, scope: Scope, env) -> Expression:
        """Expression that may contain uncorrelated scalar subqueries."""
        def lower(x):
            if isinstance(x, A.ScalarSubquery):
                from spark_rapids_tpu.session import DataFrame
                p, _ = self._select(x.query, env, outer=None)
                rows = DataFrame(p, self.session).collect()
                if not rows:
                    return lit(None)
                k = list(rows[0].keys())[0]
                return lit(rows[0][k])
            return None
        from spark_rapids_tpu.expressions.base import fold_constants
        return fold_constants(self._expr_generic(e, lower, scope))

    def _expr_generic(self, e: A.SqlExpr, leaf_hook, scope: Optional[Scope]
                      ) -> Expression:
        if leaf_hook is not None:
            got = leaf_hook(e)
            if got is not None:
                return got

        def rec(x):
            return self._expr_generic(x, leaf_hook, scope)

        if isinstance(e, A.Literal):
            if e.kind == "date":
                return Cast(lit(e.value), T.DATE)
            if e.kind == "timestamp":
                return Cast(lit(e.value), T.TIMESTAMP)
            return lit(e.value)
        if isinstance(e, A.IntervalLit):
            raise AnalysisError("INTERVAL is only valid in +/- with a date")
        if isinstance(e, A.ColumnRef):
            if scope is None:
                raise AnalysisError(f"no scope for column {e.name}")
            return scope.resolve(e.name, e.qualifier).ref()
        if isinstance(e, A.Alias):
            return Alias(rec(e.expr), e.name)
        if isinstance(e, A.FieldAccess):
            from spark_rapids_tpu.expressions.collections import \
                GetStructField
            return GetStructField(rec(e.operand), e.field)
        if isinstance(e, A.UnaryOp):
            if e.op == "not":
                return PR.Not(rec(e.operand))
            if e.op == "-":
                return AR.UnaryMinus(rec(e.operand))
            return rec(e.operand)
        if isinstance(e, A.BinaryOp):
            return self._binary(e, rec)
        if isinstance(e, A.IsNull):
            x = rec(e.operand)
            return PR.IsNotNull(x) if e.negated else PR.IsNull(x)
        if isinstance(e, A.Between):
            x = rec(e.operand)
            # coerce each bound like a standalone comparison would —
            # timestamp BETWEEN date-typed bounds must not compare
            # micros against day numbers
            x1, lo = self._coerce_pair(x, rec(e.low))
            x2, hi = self._coerce_pair(x, rec(e.high))
            inside = PR.And(PR.GreaterThanOrEqual(x1, lo),
                            PR.LessThanOrEqual(x2, hi))
            return PR.Not(inside) if e.negated else inside
        if isinstance(e, A.InList):
            x = rec(e.operand)
            opts = [rec(v) for v in e.values]
            res = PR.In(x, opts)
            return PR.Not(res) if e.negated else res
        if isinstance(e, A.Like):
            res = ST.Like(rec(e.operand), lit(e.pattern))
            return PR.Not(res) if e.negated else res
        if isinstance(e, A.Cast):
            return Cast(rec(e.expr), _parse_type(e.type_name))
        if isinstance(e, A.Case):
            if e.operand is not None:
                op = rec(e.operand)
                branches = [(PR.EqualTo(op, rec(w)), rec(t))
                            for w, t in e.branches]
            else:
                branches = [(rec(w), rec(t)) for w, t in e.branches]
            other = rec(e.otherwise) if e.otherwise is not None else None
            return CO.CaseWhen(branches, other)
        if isinstance(e, A.FuncCall):
            return self._func(e, rec)
        if isinstance(e, (A.ScalarSubquery, A.Exists, A.InSubquery)):
            raise AnalysisError(
                "subquery is not supported in this position")
        raise AnalysisError(f"unsupported expression {e}")

    def _binary(self, e: A.BinaryOp, rec) -> Expression:
        # date +/- interval and date arithmetic
        if e.op in ("+", "-"):
            if isinstance(e.right, A.IntervalLit):
                base = rec(e.left)
                iv = e.right
                if iv.unit == "day":
                    n = iv.value if e.op == "+" else -iv.value
                    return DT.DateAdd(base, lit(n))
                months = iv.value * (12 if iv.unit == "year" else 1)
                if e.op == "-":
                    months = -months
                return DT.AddMonths(base, lit(months))
            if isinstance(e.left, A.IntervalLit):
                if e.op == "-":
                    raise AnalysisError("interval - date is invalid")
                return self._binary(A.BinaryOp("+", e.right, e.left), rec)
        l = rec(e.left)
        r = rec(e.right)
        if e.op == "+":
            if isinstance(l.data_type, T.DateType):
                return DT.DateAdd(l, r)
            return AR.Add(l, r)
        if e.op == "-":
            if isinstance(l.data_type, T.DateType) and \
                    isinstance(r.data_type, T.DateType):
                return DT.DateDiff(l, r)
            if isinstance(l.data_type, T.DateType):
                return DT.DateSub(l, r)
            return AR.Subtract(l, r)
        if e.op == "*":
            return AR.Multiply(l, r)
        if e.op == "/":
            # Spark: non-decimal division is double division
            if not (isinstance(l.data_type, T.DecimalType) or
                    isinstance(r.data_type, T.DecimalType)):
                if not isinstance(l.data_type, T.DoubleType):
                    l = Cast(l, T.DOUBLE)
                if not isinstance(r.data_type, T.DoubleType):
                    r = Cast(r, T.DOUBLE)
            return AR.Divide(l, r)
        if e.op == "%":
            return AR.Remainder(l, r)
        if e.op == "||":
            return ST.Concat(l, r)
        cmp = {"=": PR.EqualTo, "<>": PR.NotEqual, "<": PR.LessThan,
               "<=": PR.LessThanOrEqual, ">": PR.GreaterThan,
               ">=": PR.GreaterThanOrEqual}
        if e.op in cmp:
            l2, r2 = self._coerce_pair(l, r)
            return cmp[e.op](l2, r2)
        if e.op == "and":
            return PR.And(l, r)
        if e.op == "or":
            return PR.Or(l, r)
        raise AnalysisError(f"unsupported operator {e.op}")

    _SIMPLE_FUNCS = None

    @classmethod
    def _simple_funcs(cls):
        if cls._SIMPLE_FUNCS is None:
            cls._SIMPLE_FUNCS = {
                "abs": AR.Abs, "ceil": MA.Ceil, "ceiling": MA.Ceil,
                "floor": MA.Floor, "sqrt": lambda x: MA.Pow(x, lit(0.5)),
                "upper": ST.Upper, "ucase": ST.Upper,
                "lower": ST.Lower, "lcase": ST.Lower,
                "length": ST.Length, "char_length": ST.Length,
                "trim": ST.Trim, "ltrim": ST.LTrim, "rtrim": ST.RTrim,
                "reverse": ST.Reverse, "initcap": ST.InitCap,
                "year": DT.Year, "month": DT.Month,
                "quarter": DT.Quarter, "day": DT.DayOfMonth,
                "dayofmonth": DT.DayOfMonth, "dayofweek": DT.DayOfWeek,
                "dayofyear": DT.DayOfYear, "hour": DT.Hour,
                "minute": DT.Minute, "second": DT.Second,
                "last_day": DT.LastDay, "signum": MA.Signum,
                "isnull": PR.IsNull, "isnotnull": PR.IsNotNull,
            }
        return cls._SIMPLE_FUNCS

    def _func(self, e: A.FuncCall, rec) -> Expression:
        name = e.name
        if e.window is not None:
            return self._window_call(e, rec)
        if name in _AGG_FUNCS:
            raise AnalysisError(
                f"aggregate {name}() used outside GROUP BY context")
        args = [rec(a) for a in e.args]
        simple = self._simple_funcs()
        if name in simple and len(args) == 1:
            return simple[name](args[0])
        if name == "substr":
            if len(args) == 2:
                return ST.Substring(args[0], args[1])
            return ST.Substring(args[0], args[1], args[2])
        if name == "coalesce":
            return CO.Coalesce(*args)
        if name == "nullif":
            return CO.If(PR.EqualTo(args[0], args[1]), lit(None), args[0])
        if name == "nvl" or name == "ifnull":
            return CO.Coalesce(args[0], args[1])
        if name == "if":
            return CO.If(*args)
        if name == "concat":
            return ST.Concat(*args)
        if name == "round":
            return MA.Round(args[0], args[1] if len(args) > 1 else lit(0))
        if name == "power" or name == "pow":
            return MA.Pow(args[0], args[1])
        if name == "greatest":
            return CO.Greatest(*args)
        if name == "least":
            return CO.Least(*args)
        if name == "date_add":
            return DT.DateAdd(args[0], args[1])
        if name == "date_sub":
            return DT.DateSub(args[0], args[1])
        if name == "datediff":
            return DT.DateDiff(args[0], args[1])
        if name == "add_months":
            return DT.AddMonths(args[0], args[1])
        if name == "months_between":
            return DT.MonthsBetween(args[0], args[1])
        if name == "lpad":
            return ST.LPad(args[0], args[1], args[2] if len(args) > 2
                           else lit(" "))
        if name == "rpad":
            return ST.RPad(args[0], args[1], args[2] if len(args) > 2
                           else lit(" "))
        if name == "struct":
            from spark_rapids_tpu.expressions.collections import \
                CreateNamedStruct
            return CreateNamedStruct([f"col{i + 1}" for i in
                                      range(len(args))], args)
        if name == "named_struct":
            from spark_rapids_tpu.expressions.base import Literal as _L
            from spark_rapids_tpu.expressions.collections import \
                CreateNamedStruct
            if len(args) % 2:
                raise AnalysisError("named_struct needs name/value pairs")
            names2 = []
            for a in args[0::2]:
                if not isinstance(a, _L):
                    raise AnalysisError(
                        "named_struct field names must be literals")
                names2.append(str(a.value))
            return CreateNamedStruct(names2, args[1::2])
        if name == "sort_array":
            from spark_rapids_tpu.expressions.collections import SortArray
            return SortArray(args[0],
                             args[1] if len(args) > 1 else None)
        if name == "size" or name == "cardinality":
            from spark_rapids_tpu.expressions.collections import Size
            return Size(args[0])
        if name == "array_contains":
            from spark_rapids_tpu.expressions.collections import \
                ArrayContains
            return ArrayContains(args[0], args[1])
        if name == "hash":
            from spark_rapids_tpu.expressions.hashing import Murmur3Hash
            return Murmur3Hash(*args)
        if name == "xxhash64":
            from spark_rapids_tpu.expressions.hashing import XxHash64
            return XxHash64(*args)
        hive_udf = getattr(self.session, "_hive_udfs", {}).get(name)
        if hive_udf is not None:
            # row-based Hive UDF passthrough (rowBasedHiveUDFs.scala)
            from spark_rapids_tpu.udf.api import PythonRowUDF
            fn, rt = hive_udf
            return PythonRowUDF(fn, rt, args, name=name)
        raise AnalysisError(f"unknown function {name}")

    def _window_call(self, e: A.FuncCall, rec) -> Expression:
        if e.distinct:
            # Spark rejects DISTINCT inside window functions too;
            # silently computing the non-distinct form would be worse
            raise AnalysisError(
                f"DISTINCT is not allowed in window function "
                f"{e.name}() OVER (...)")
        w = e.window
        part = [rec(p) for p in w.partition_by]
        order = []
        for it in w.order_by:
            asc = it.ascending
            nf = it.nulls_first if it.nulls_first is not None else asc
            order.append((rec(it.expr), asc, nf))
        frame = None
        if w.frame is not None:
            kind, start, end = w.frame
            frame = WX.WindowFrame(kind=kind, lo=self._bound(start),
                                   hi=self._bound(end))
        spec = WX.WindowSpecDef(part, order, frame)
        args = [rec(a) for a in e.args]
        wmap = {"row_number": WX.RowNumber, "rank": WX.Rank,
                "dense_rank": WX.DenseRank}
        if e.name in wmap:
            fn = wmap[e.name]()
        elif e.name == "ntile":
            fn = WX.NTile(int(e.args[0].value))
        elif e.name == "lag":
            fn = WX.Lag(args[0], int(e.args[1].value) if len(args) > 1
                        else 1)
        elif e.name == "lead":
            fn = WX.Lead(args[0], int(e.args[1].value) if len(args) > 1
                         else 1)
        elif e.name in _AGG_FUNCS:
            fn = self._agg_from_parts(e.name, args)
        else:
            raise AnalysisError(f"unknown window function {e.name}")
        return fn.over(spec)

    def _agg_from_parts(self, name, args):
        m = {"sum": AG.Sum, "avg": AG.Average, "count": AG.Count,
             "min": AG.Min, "max": AG.Max}
        if name not in m:
            raise AnalysisError(f"{name} is not a window aggregate")
        arg = args[0] if args else lit(1)
        return m[name](arg)

    def _bound(self, text: str):
        if text == "unbounded preceding":
            return WX.UNBOUNDED_PRECEDING
        if text == "unbounded following":
            return WX.UNBOUNDED_FOLLOWING
        if text == "current row":
            return WX.CURRENT_ROW
        n, kind = text.split()
        v = int(n)
        return -v if kind == "preceding" else v
