"""Partition-distribution analysis + redundant-exchange elision.

Spark's EnsureRequirements inserts an exchange wherever a node's
required distribution is not already delivered by its child; our
DataFrame/SQL layers instead insert exchanges EAGERLY (every join and
two-stage aggregate shuffles), so the dual pass lives here: propagate
the *delivered* distribution bottom-up through every exec and DELETE
the exchanges whose requirement the child already satisfies.  That is
where the distributed deficit lives (ROADMAP item 3 / Theseus,
PAPERS.md: data movement, not compute, dominates) — a co-partitioned
join re-shuffled both sides, and an aggregate above it re-shuffled the
join output over the very same keys.

The lattice (GpuPartitioning / Spark Distribution analog):

- ``UnknownDist``   — nothing known (scans, unions, round-robin).
- ``SingleDist``    — all rows in one partition.
- ``HashDist(keys, n)``  — row r lives in partition
  ``pmod(murmur3(keys(r)), n)`` (bit-exact Spark placement, so two
  sides delivering the same ``HashDist`` are co-partitioned pairwise).
- ``RangeDist(specs, n)`` — partitions hold consecutive key ranges in
  sort order (bounds may differ between producers; consumers of a
  range exchange only rely on the ordering property).

``mesh_axis`` is the NamedSharding analog: when the active mesh has
exactly ``n`` devices a hash distribution is additionally *bound* to
the mesh's data axis — partition p IS device p's shard, which is what
lets the in-mesh exchange (parallel/spmd.py) keep shuffled data
device-resident and lets downstream stages run on their shard without
any transfer.

Key expressions are compared by a canonical structural form over bound
ordinals (``canon``), remapped through projections/aggregate keys as
the distribution flows up, so renames and Alias wrappers cannot break
(or spuriously allow) a match.

The pass is gated by ``spark.rapids.sql.distribution.enabled``; when
off the plan is returned untouched (bit-for-bit today's trees — pinned
by tests/test_distribution.py).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Tuple

from spark_rapids_tpu.plan.base import Exec

__all__ = ["canon", "HashDist", "RangeDist", "SingleDist",
           "delivered_dists", "required_dist",
           "eliminate_redundant_exchanges", "Elision"]


# ---------------------------------------------------------------------------
# the lattice
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SingleDist:
    def desc(self) -> str:
        return "single"


@dataclasses.dataclass(frozen=True)
class HashDist:
    keys: Tuple            # tuple of canonical key forms, in hash order
    n: int
    #: mesh data-axis name when partition i is device i's shard (the
    #: NamedSharding-style binding); purely descriptive for matching —
    #: two hash distributions co-partition regardless of residency
    mesh_axis: Optional[str] = None

    def desc(self) -> str:
        ax = f"@{self.mesh_axis}" if self.mesh_axis else ""
        return f"hash[{len(self.keys)}k,{self.n}]{ax}"

    def matches(self, other: "HashDist") -> bool:
        return self.keys == other.keys and self.n == other.n


@dataclasses.dataclass(frozen=True)
class RangeDist:
    specs: Tuple           # ((canon key, ascending, nulls_first), ...)
    n: int

    def desc(self) -> str:
        return f"range[{len(self.specs)}k,{self.n}]"


# ---------------------------------------------------------------------------
# canonical key forms
# ---------------------------------------------------------------------------

def canon(e) -> Tuple:
    """Canonical structural form of a bound expression: Alias-transparent,
    ordinals for references, literals by (type, value).  Two expressions
    with equal canon forms evaluate identically over the same input
    batch — the equivalence hash-partition matching needs."""
    from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                                   Literal)
    if isinstance(e, Alias):
        return canon(e.children[0])
    if isinstance(e, BoundReference):
        return ("ref", e.ordinal)
    if isinstance(e, Literal):
        return ("lit", str(e.data_type), repr(e.value))
    return (type(e).__name__,) + tuple(canon(c) for c in e.children)


def _shift_refs(form: Tuple, by: int) -> Tuple:
    if not isinstance(form, tuple):
        return form
    if form and form[0] == "ref":
        return ("ref", form[1] + by)
    return tuple(_shift_refs(f, by) if isinstance(f, tuple) else f
                 for f in form)


def _remap(form: Tuple, out_map: Dict[Tuple, Tuple]) -> Optional[Tuple]:
    """Re-expresses a canonical key over a node's OUTPUT ordinals given
    ``out_map`` (canonical child-space expression -> ("ref", j)).  An
    exact projected column wins; otherwise the form survives only if
    every reference inside it is itself projected through.  Returns
    None when the key's inputs do not survive the node."""
    if form in out_map:
        return out_map[form]
    if not isinstance(form, tuple) or not form:
        return form
    if form[0] == "ref":
        return None            # bare reference not passed through
    if form[0] == "lit":
        return form
    head, rest = form[0], form[1:]
    mapped = []
    for f in rest:
        m = _remap(f, out_map) if isinstance(f, tuple) else f
        if m is None:
            return None
        mapped.append(m)
    return (head,) + tuple(mapped)


def _remap_dists(dists, out_exprs) -> FrozenSet:
    """Pushes a delivered-distribution set through a projection-like node
    whose output column j computes ``out_exprs[j]`` over the child."""
    return _remap_by_map(dists, {canon(e): ("ref", j)
                                 for j, e in enumerate(out_exprs)})


# ---------------------------------------------------------------------------
# delivered distributions, bottom-up
# ---------------------------------------------------------------------------

def _mesh_axis_for(n: int) -> Optional[str]:
    from spark_rapids_tpu.parallel.mesh import active_mesh
    ctx = active_mesh()
    if ctx is not None and ctx.num_devices == n:
        return ctx.data_axis
    return None


def required_dist(partitioning):
    """The distribution an exchange with ``partitioning`` delivers —
    equally, what its consumer requires of it."""
    from spark_rapids_tpu.plan.partitioning import (HashPartitioning,
                                                    RangePartitioning,
                                                    SinglePartitioning)
    if isinstance(partitioning, SinglePartitioning):
        return SingleDist()
    if isinstance(partitioning, HashPartitioning):
        return HashDist(tuple(canon(k) for k in partitioning.key_exprs),
                        partitioning.num_partitions,
                        _mesh_axis_for(partitioning.num_partitions))
    if isinstance(partitioning, RangePartitioning):
        return RangeDist(tuple((canon(s.expr), s.ascending,
                                s.effective_nulls_first)
                               for s in partitioning.specs),
                         partitioning.num_partitions)
    return None      # round-robin: placement is positional, never reusable


def delivered_dists(node: Exec,
                    memo: Optional[Dict[int, FrozenSet]] = None
                    ) -> FrozenSet:
    """The set of distributions ``node``'s output provably satisfies.
    Handles BOTH the pre-convert Cpu tree (where the elision pass runs)
    and the final mixed Cpu/Tpu tree (where plan/verify.py re-checks),
    by duck-typing the few structural shapes that matter and treating
    everything else as unknown."""
    if memo is None:
        memo = {}
    key = id(node)
    if key in memo:
        return memo[key]
    memo[key] = frozenset()     # cycle guard (plans are DAGs, not cycles)
    out = _delivered(node, memo)
    if node.num_partitions == 1:
        out = out | {SingleDist()}
    memo[key] = out
    return out


def _child_dists(node: Exec, memo) -> FrozenSet:
    return delivered_dists(node.children[0], memo) if node.children \
        else frozenset()


def _delivered(node: Exec, memo) -> FrozenSet:    # noqa: C901 - dispatch
    import spark_rapids_tpu.ops.join_ops as J
    from spark_rapids_tpu.exec import basic as XB
    from spark_rapids_tpu.exec.aggregate import (FINAL,
                                                 CpuHashAggregateExec)
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.exec.joins import (CpuBroadcastHashJoinExec,
                                             CpuShuffledHashJoinExec,
                                             TpuBroadcastHashJoinExec,
                                             TpuShuffledHashJoinExec)

    # -- exchanges: the distribution producers --------------------------
    if isinstance(node, CpuShuffleExchangeExec):
        d = required_dist(node.partitioning)
        return frozenset([d]) if d is not None else frozenset()

    # -- aggregates: keys become output columns 0..nk-1 -----------------
    if isinstance(node, CpuHashAggregateExec):
        child = _child_dists(node, memo)
        if node.mode == FINAL:
            # child is the buffer layout: keys already sit at 0..nk-1
            # and pass through to the result schema positionally
            out_map = {("ref", i): ("ref", i)
                       for i in range(node.layout.num_keys)}
            return _remap_by_map(child, out_map)
        return _remap_dists(child, node.layout.grouping)

    # -- joins: partition i pairs with partition i ----------------------
    if isinstance(node, (CpuShuffledHashJoinExec, TpuShuffledHashJoinExec,
                         CpuBroadcastHashJoinExec,
                         TpuBroadcastHashJoinExec)):
        jt = node.join_type
        left = delivered_dists(node.children[0], memo)
        # over left ++ right, then through the join's output selection
        out = set()
        if jt in (J.INNER, J.LEFT_OUTER, J.LEFT_SEMI, J.LEFT_ANTI):
            out |= {d for d in left if not isinstance(d, SingleDist)}
        # semi/anti emit the left schema only; broadcast replicates the
        # build side, so only the stream side's placement holds
        if jt in (J.INNER, J.RIGHT_OUTER) and not isinstance(
                node, (CpuBroadcastHashJoinExec, TpuBroadcastHashJoinExec)):
            nl = len(node.children[0].schema.fields)
            for d in delivered_dists(node.children[1], memo):
                if isinstance(d, HashDist):
                    out.add(HashDist(tuple(_shift_refs(k, nl)
                                           for k in d.keys),
                                     d.n, d.mesh_axis))
        if node.selection is None:
            return frozenset(out)
        # a distribution whose key column the join does not hand on is
        # no longer delivered
        return _remap_by_map(out, {("ref", o): ("ref", j)
                                   for j, o in enumerate(node.selection)})

    # -- projections (both tiers) ---------------------------------------
    if isinstance(node, XB.CpuProjectExec) or \
            isinstance(node, XB.TpuProjectExec):
        return _remap_dists(_child_dists(node, memo), node.exprs)
    if isinstance(node, XB.TpuFilterProjectExec):
        return _remap_dists(_child_dists(node, memo), node.exprs)

    # -- fused stages: fold the op chain in execution order -------------
    from spark_rapids_tpu.exec.fused import (TpuFusedAggExec,
                                             TpuFusedStageExec)
    if isinstance(node, TpuFusedStageExec):
        return _fold_ops(_child_dists(node, memo), node.ops)
    if isinstance(node, TpuFusedAggExec):
        dists = _fold_ops(_child_dists(node, memo), node.ops)
        return _remap_dists(dists, node.layout.grouping)

    # -- row/partition-preserving unary nodes ---------------------------
    if _is_transparent(node):
        return _child_dists(node, memo)

    return frozenset()


def _remap_by_map(dists, out_map) -> FrozenSet:
    out = set()
    for d in dists:
        if isinstance(d, SingleDist):
            out.add(d)
        elif isinstance(d, HashDist):
            keys = tuple(_remap(k, out_map) for k in d.keys)
            if all(k is not None for k in keys):
                out.add(HashDist(keys, d.n, d.mesh_axis))
        elif isinstance(d, RangeDist):
            specs = tuple((_remap(k, out_map), a, nf)
                          for k, a, nf in d.specs)
            if all(k is not None for k, _a, _n in specs):
                out.add(RangeDist(specs, d.n))
    return frozenset(out)


def _fold_ops(dists: FrozenSet, ops) -> FrozenSet:
    """Delivered distributions through a fused filter/project chain (ops
    in execution order; filters preserve, projects remap)."""
    for kind, payload in ops:
        if kind == "project":
            dists = _remap_dists(dists, payload)
    return dists


def _is_transparent(node: Exec) -> bool:
    """Unary nodes that neither move rows across partitions nor change
    the ordinals of existing columns (appended columns are fine)."""
    from spark_rapids_tpu.exec import basic as XB
    from spark_rapids_tpu.exec.sort import CpuSortExec, TpuSortExec
    from spark_rapids_tpu.exec.window import CpuWindowExec
    transparent = (XB.CpuFilterExec, XB.TpuFilterExec, XB.CpuLimitExec,
                   XB.TpuLimitExec, XB.CpuGlobalLimitExec,
                   XB.CpuCteCacheExec, XB.CpuSampleExec, XB.TpuSampleExec,
                   XB.TpuCoalesceBatchesExec, XB.HostToDeviceExec,
                   XB.DeviceToHostExec, XB.TpuMaterializeEncodedExec,
                   CpuSortExec, TpuSortExec, CpuWindowExec)
    if isinstance(node, transparent):
        return True
    try:
        from spark_rapids_tpu.exec.pipeline import PrefetchExec
        if isinstance(node, PrefetchExec):
            return True
    except ImportError:           # pragma: no cover - pipeline always ships
        pass
    return False


# ---------------------------------------------------------------------------
# the elision pass
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Elision:
    """One removed exchange, for events/EXPLAIN."""
    partitioning: str
    delivered: str

    def desc(self) -> str:
        return f"{self.partitioning} <= {self.delivered}"


def _satisfied(required, dists) -> Optional[str]:
    """Returns the delivered distribution's desc when ``required`` is
    already met, else None."""
    for d in dists:
        if isinstance(required, SingleDist) and isinstance(d, SingleDist):
            return d.desc()
        if isinstance(required, HashDist) and isinstance(d, HashDist) \
                and required.matches(d):
            return d.desc()
        if isinstance(required, RangeDist) and isinstance(d, RangeDist) \
                and required.specs == d.specs and required.n == d.n:
            return d.desc()
    return None


def eliminate_redundant_exchanges(plan: Exec
                                  ) -> Tuple[Exec, List[Elision]]:
    """Removes every shuffle exchange whose child already delivers the
    required distribution (same hash keys AND partition count — the
    murmur3-pmod placement is deterministic, so equal distributions mean
    equal partition assignment, not merely co-grouping).  Runs on the
    pre-convert Cpu tree; identity-memoized so DAG-shared subtrees
    (CTE reuse) stay shared."""
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.plan.partitioning import RoundRobinPartitioning

    elided: List[Elision] = []
    memo: Dict[int, Exec] = {}
    dist_memo: Dict[int, FrozenSet] = {}

    def visit(node: Exec) -> Exec:
        key = id(node)
        if key in memo:
            return memo[key]
        new_children = [visit(c) for c in node.children]
        out = node if all(a is b for a, b in zip(new_children,
                                                 node.children)) \
            else node.with_children(new_children)
        if isinstance(out, CpuShuffleExchangeExec) and \
                not isinstance(out.partitioning, RoundRobinPartitioning):
            required = required_dist(out.partitioning)
            child = out.children[0]
            if required is not None and \
                    child.num_partitions == out.num_partitions:
                got = _satisfied(required, delivered_dists(child,
                                                           dist_memo))
                if got is not None:
                    elided.append(Elision(out.partitioning.desc(), got))
                    out = child
        memo[key] = out
        return out

    return visit(plan), elided
