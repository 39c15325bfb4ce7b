"""Column pruning (scan-level projection pushdown).

Walks the physical plan top-down computing the set of child output ordinals
each node actually consumes, rebuilds bottom-up remapping BoundReference
ordinals, and asks leaf scans to drop unused columns.  On TPU this is a
first-order win: every pruned column is a host->device transfer that never
happens (the transfer's fixed cost dominates at batch sizes, see
columnar/transfer.py).

Reference analog: Spark performs column pruning in the logical optimizer
before the plan ever reaches GpuOverrides; since this engine builds physical
plans directly from the DataFrame/SQL API, the pass lives here.  The
reference's scan-side nested-schema pruning lives in
sql-plugin/.../GpuParquetScan.scala (clipped schemas).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from spark_rapids_tpu.expressions.base import BoundReference, Expression
from spark_rapids_tpu.plan.base import Exec


def _refs(e: Optional[Expression], into: Set[int]):
    if e is None:
        return
    for n in e.collect(lambda x: isinstance(x, BoundReference)):
        into.add(n.ordinal)


def _remap(e: Expression, mapping: Dict[int, int]) -> Expression:
    def fix(n):
        if isinstance(n, BoundReference):
            return BoundReference(mapping[n.ordinal], n._dtype, n._nullable,
                                  n.ref_name)
        return n
    return e.transform_up(fix)


def _identity(n: int) -> Dict[int, int]:
    return {i: i for i in range(n)}


class _Pruner:
    """One pruning rewrite over a plan tree."""

    def prune(self, node: Exec,
              required: Optional[Set[int]]) -> Tuple[Exec, Dict[int, int]]:
        """Returns (new_node, mapping old output ordinal -> new ordinal).

        ``required`` is the set of this node's output ordinals the parent
        consumes (None = all).  The mapping's key set always covers at least
        ``required``.
        """
        from spark_rapids_tpu.exec import basic as B
        from spark_rapids_tpu.exec import joins as JX
        from spark_rapids_tpu.exec import sort as S
        from spark_rapids_tpu.exec import aggregate as AG
        from spark_rapids_tpu.exec import exchange as EX
        from spark_rapids_tpu.exec import expand as EP

        ncols = len(node.schema.fields)
        if required is not None and len(required) >= ncols:
            required = None

        if isinstance(node, B.CpuProjectExec):
            exprs = node.exprs
            keep = sorted(required) if required is not None \
                else list(range(len(exprs)))
            kept = [exprs[i] for i in keep]
            child_req: Set[int] = set()
            for e in kept:
                _refs(e, child_req)
            child, cmap = self.prune(node.child, child_req)
            new = B.CpuProjectExec([_remap(e, cmap) for e in kept], child)
            return new, {o: i for i, o in enumerate(keep)}

        if isinstance(node, B.CpuFilterExec):
            child_req = set(required) if required is not None else None
            if child_req is not None:
                _refs(node.condition, child_req)
            child, cmap = self.prune(node.child, child_req)
            new = B.CpuFilterExec(_remap(node.condition, cmap), child)
            return new, cmap

        if isinstance(node, S.CpuSortExec):
            child_req = set(required) if required is not None else None
            if child_req is not None:
                for sp in node.specs:
                    _refs(sp.expr, child_req)
            child, cmap = self.prune(node.child, child_req)
            specs = [dataclasses_replace_spec(sp, _remap(sp.expr, cmap))
                     for sp in node.specs]
            new = S.CpuSortExec(specs, child, node.global_sort)
            return new, cmap

        if isinstance(node, EX.CpuShuffleExchangeExec):
            part = node.partitioning
            pexprs = getattr(part, "key_exprs", None)
            pspecs = getattr(part, "specs", None)
            child_req = set(required) if required is not None else None
            if child_req is not None:
                for e in (pexprs or []):
                    _refs(e, child_req)
                for sp in (pspecs or []):
                    _refs(sp.expr, child_req)
            child, cmap = self.prune(node.child, child_req)
            import copy
            npart = copy.copy(part)
            if pexprs is not None:
                npart.key_exprs = [_remap(e, cmap) for e in pexprs]
            if pspecs is not None:
                npart.specs = [dataclasses_replace_spec(sp,
                                                        _remap(sp.expr, cmap))
                               for sp in pspecs]
            new = EX.CpuShuffleExchangeExec(npart, child, node.shuffle_env)
            return new, cmap

        if isinstance(node, AG.CpuHashAggregateExec) and \
                type(node) is AG.CpuHashAggregateExec:
            layout = node.layout
            if node.mode not in (AG.PARTIAL, AG.COMPLETE):
                # FINAL/merge mode consumes the positional BUFFER schema of
                # its child (keys ++ agg buffers) — the layout's func/
                # grouping ordinals are bound against the pre-partial raw
                # input, a different schema space, so remapping them with
                # the child's map would corrupt them (and every buffer
                # column is required anyway).  Recurse keeping all child
                # columns; pruning continues below the exchange.
                child, _ = self.prune(node.child, None)
                return node.with_children([child]), _identity(ncols)
            child_req = set()
            for e in layout.grouping:
                _refs(e, child_req)
            for a in layout.aggs:
                _refs(a.func, child_req)
            child, cmap = self.prune(node.child, child_req)
            import dataclasses as dc
            grouping = [_remap(e, cmap) for e in layout.grouping]
            aggs = [dc.replace(a, func=_remap(a.func, cmap))
                    for a in layout.aggs]
            new = AG.CpuHashAggregateExec(grouping, aggs, node.mode, child)
            return new, _identity(ncols)

        if type(node) is EP.CpuExpandExec:
            # every projection keeps the columns the parent reads (one at
            # least: a batch of no column loses its row count); the child
            # delivers what any of them reads
            keep = sorted(required) if required is not None \
                else list(range(ncols))
            keep = keep or [0]
            child_req = set()
            for proj in node.projections:
                for j in keep:
                    _refs(proj[j], child_req)
            child, cmap = self.prune(node.child, child_req)
            new = EP.CpuExpandExec([[_remap(proj[j], cmap) for j in keep]
                                    for proj in node.projections],
                                   [node.names[j] for j in keep], child)
            return new, {o: i for i, o in enumerate(keep)}

        if isinstance(node, JX._CpuJoinCore) and type(node) in (
                JX.CpuShuffledHashJoinExec, JX.CpuBroadcastHashJoinExec,
                JX.CpuBroadcastNestedLoopJoinExec):
            return self._prune_join(node, required)

        # pass-through nodes: schema == child schema, rows subset/identical
        if type(node) in (B.CpuLimitExec, B.CpuGlobalLimitExec,
                          B.CpuCoalescePartitionsExec, B.CpuSampleExec):
            child, cmap = self.prune(node.children[0], required)
            return node.with_children([child]), cmap

        # leaf scans that support pruning
        if not node.children:
            if required is not None:
                pruned = prune_scan(node, sorted(required))
                if pruned is not None:
                    return pruned, {o: i for i, o in
                                    enumerate(sorted(required))}
            return node, _identity(ncols)

        # barrier: unknown node — recurse requiring everything
        children = [self.prune(c, None)[0] for c in node.children]
        return node.with_children(children), _identity(ncols)

    def _prune_join(self, node, required: Optional[Set[int]]):
        """The children deliver what the parent reads plus the keys and
        the condition's columns; the join hands on only what the parent
        reads (its ``selection``), so a key or a filter-only column no
        consumer names is never gathered at the pair bucket."""
        import spark_rapids_tpu.ops.join_ops as J
        nl = len(node.left.schema.fields)
        # the parent's ordinals over the join's output, and the columns of
        # left ++ right they name, in output order
        pair_of = node.selection if node.selection is not None \
            else range(len(node._pair_fields()))
        reads = range(len(pair_of)) if required is None else sorted(required)
        out = [pair_of[o] for o in reads]
        lreq: Set[int] = {o for o in out if o < nl}
        rreq: Set[int] = {o - nl for o in out if o >= nl}
        for e in node.left_keys:
            _refs(e, lreq)
        for e in node.right_keys:
            _refs(e, rreq)
        cond_refs: Set[int] = set()
        _refs(node.condition, cond_refs)
        for o in cond_refs:
            if o < nl:
                lreq.add(o)
            else:
                rreq.add(o - nl)

        left, lmap = self.prune(node.left, lreq)
        right, rmap = self.prune(node.right, rreq)
        nl_new = len(left.schema.fields)

        def pair_map(o: int) -> int:
            return lmap[o] if o < nl else nl_new + rmap[o - nl]

        cond = None if node.condition is None else \
            _remap(node.condition, {o: pair_map(o) for o in cond_refs})
        width = nl_new + (0 if node.join_type in (J.LEFT_SEMI, J.LEFT_ANTI)
                          else len(right.schema.fields))
        selection = [pair_map(o) for o in out]
        out_map = {o: j for j, o in enumerate(reads)}
        if not selection and width:
            # a batch of no column loses its row count: keep the first
            selection = [0]
        new = type(node)(
            [_remap(e, lmap) for e in node.left_keys],
            [_remap(e, rmap) for e in node.right_keys],
            node.join_type, cond, left, right, node.null_safe,
            None if selection == list(range(width)) else selection)
        return new, out_map


def dataclasses_replace_spec(sp, new_expr):
    import dataclasses as dc
    return dc.replace(sp, expr=new_expr)


def prune_scan(scan: Exec, indices: List[int]) -> Optional[Exec]:
    """Asks a leaf node for a column-subset clone; None if unsupported."""
    fn = getattr(scan, "with_pruned_columns", None)
    if fn is None:
        return None
    return fn(indices)


def prune_columns(plan: Exec, required: Optional[Set[int]] = None,
                  strict: bool = False) -> Exec:
    """Entry point: prunes unused columns below the root.

    ``required=None`` keeps the root's full output; an explicit set narrows
    it (count() passes an empty set: only row counts survive).  ``strict``
    (test mode) re-raises instead of silently executing unpruned — a
    pruning crash is a modeling bug, not an acceptable steady state.
    """
    import logging
    try:
        new, _ = _Pruner().prune(plan, required)
        return new
    except Exception:
        if strict:
            raise
        # pruning is an optimization; never let it break planning
        logging.getLogger(__name__).warning(
            "column pruning failed; executing unpruned plan", exc_info=True)
        return plan
