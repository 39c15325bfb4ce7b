"""Broadcast or shuffle: how an equi-join is planned.

Reference: Spark's ``JoinSelection`` strategy, the part that needs no
cost-based optimizer.  A side whose estimated size is at most
``spark.sql.autoBroadcastJoinThreshold`` is the build side of a broadcast
hash join and neither side is exchanged: the other side keeps its
partitions and every task of it probes the one build.  Which side may be
built follows Spark's ``BuildRight`` / ``BuildLeft`` table: the right side
for inner, left outer, left semi and left anti joins; the left side for
inner and right outer joins; a full outer join is always shuffled.  The
broadcast exec builds its right child, so a join that builds its left side
is planned with the sides swapped (a right outer join as the left outer
join of the swapped sides) under a projection that restores the columns to
the text's order.

The estimate is Spark's default statistic (``sizeInBytes`` without column
statistics): the bytes of an in-memory relation's referenced columns, or
the sizes of a file scan's files, carried unchanged through a filter and
scaled by the row's width through a projection.  A side that is anything
else (a join, an aggregate, a union) has no estimate and is never
broadcast by size.  The SQL analyzer and ``DataFrame.join`` both plan
through :func:`plan_equi_join`, so there is one rule.  With one partition
on both sides there is no exchange to save and the join stays the plain
hash join it always was.
"""

from __future__ import annotations

from typing import Optional, Sequence, Set

import spark_rapids_tpu.ops.join_ops as J
from spark_rapids_tpu import config as C
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression)
from spark_rapids_tpu.plan.base import Exec

#: join types whose right / left side may be the broadcast build side
BUILD_RIGHT = (J.INNER, J.LEFT_OUTER, J.LEFT_SEMI, J.LEFT_ANTI)
BUILD_LEFT = (J.INNER, J.RIGHT_OUTER)


def _row_width(fields) -> int:
    return sum(f.data_type.default_size for f in fields)


def estimated_bytes(plan: Exec,
                    required: Optional[Set[int]] = None) -> Optional[int]:
    """Bytes ``plan`` is estimated to hold in the output columns
    ``required`` (ordinals; ``None``: all of them), or ``None`` where the
    rule has no estimate."""
    from spark_rapids_tpu.exec.basic import (CpuFilterExec,
                                             CpuInMemoryScanExec,
                                             CpuProjectExec)
    from spark_rapids_tpu.io.multifile import MultiFileScanBase
    from spark_rapids_tpu.plan.pruning import _refs
    if isinstance(plan, CpuInMemoryScanExec):
        held = plan.col_indices if plan.col_indices is not None \
            else range(len(plan._schema.fields))
        keep = [c for i, c in enumerate(held)
                if required is None or i in required]
        return sum(hb.columns[c].arrow.nbytes
                   for part in plan.partitions for hb in part for c in keep)
    if isinstance(plan, MultiFileScanBase):
        return sum(plan._file_size(p) for p in plan.paths)
    if isinstance(plan, CpuFilterExec):
        return estimated_bytes(plan.child, required)
    if isinstance(plan, CpuProjectExec):
        kept = [e for i, e in enumerate(plan.exprs)
                if required is None or i in required]
        read: Set[int] = set()
        for e in kept:
            _refs(e, read)
        below = estimated_bytes(plan.child, read)
        if below is None:
            return None
        fields = plan.child.schema.fields
        width_in = _row_width([fields[i] for i in sorted(read)])
        width_out = sum(e.data_type.default_size for e in kept)
        return below * width_out // width_in if width_in else below
    return None


def _named(plan: Exec, referenced: Optional[Set[str]]):
    """The output ordinals of ``plan`` whose names a query's text refers
    to (``referenced``: lower-case names; ``None``: every column)."""
    if referenced is None:
        return None
    return {i for i, f in enumerate(plan.schema.fields)
            if f.name.lower() in referenced}


def broadcast_side(conf, left: Exec, right: Exec, how: str,
                   referenced: Optional[Set[str]] = None) -> Optional[str]:
    """``"right"``, ``"left"`` or ``None``: the side Spark's rule would
    broadcast.  Where both sides of an inner join qualify, the smaller."""
    threshold = int(conf.get(C.AUTO_BROADCAST_JOIN_THRESHOLD.key))
    if threshold < 0:
        return None
    sized = {}
    for name, plan, hows in (("right", right, BUILD_RIGHT),
                             ("left", left, BUILD_LEFT)):
        if how not in hows:
            continue
        nbytes = estimated_bytes(plan, _named(plan, referenced))
        if nbytes is not None and nbytes <= threshold:
            sized[name] = nbytes
    if not sized:
        return None
    return min(sized, key=lambda side: (sized[side], side != "right"))


def _swapped_broadcast(left: Exec, right: Exec, lkeys, rkeys, how: str,
                       cond: Optional[Expression], null_safe) -> Exec:
    """The join that builds its LEFT side: the broadcast join of the
    swapped sides under a projection back to left-then-right."""
    from spark_rapids_tpu.exec.basic import CpuProjectExec
    from spark_rapids_tpu.exec.joins import CpuBroadcastHashJoinExec
    from spark_rapids_tpu.plan.pruning import _remap
    nl, nr = len(left.schema.fields), len(right.schema.fields)
    if cond is not None:
        cond = _remap(cond, {i: i + nr if i < nl else i - nl
                             for i in range(nl + nr)})
    join = CpuBroadcastHashJoinExec(
        rkeys, lkeys, J.LEFT_OUTER if how == J.RIGHT_OUTER else how, cond,
        right, left, null_safe)
    fields = join.schema.fields
    back = [Alias(BoundReference(i, fields[i].data_type, fields[i].nullable),
                  fields[i].name)
            for i in list(range(nr, nr + nl)) + list(range(nr))]
    return CpuProjectExec(back, join)


def plan_equi_join(session, left: Exec, right: Exec,
                   lkeys: Sequence[Expression], rkeys: Sequence[Expression],
                   how: str, cond: Optional[Expression] = None,
                   null_safe=None, broadcast_right_hint: bool = False,
                   referenced: Optional[Set[str]] = None) -> Exec:
    """The physical join of ``left`` and ``right`` on ``lkeys = rkeys``:
    a broadcast hash join where the hint or the size rule names a build
    side, else the hash join over both sides hash-exchanged by the keys
    (no exchange where both sides have one partition)."""
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.exec.joins import (CpuBroadcastHashJoinExec,
                                             CpuShuffledHashJoinExec)
    from spark_rapids_tpu.plan.partitioning import HashPartitioning
    nparts = max(left.num_partitions, right.num_partitions)
    side = None
    if broadcast_right_hint and how in BUILD_RIGHT:
        side = "right"
    elif nparts > 1:
        side = broadcast_side(session.conf, left, right, how, referenced)
    if side == "right":
        return CpuBroadcastHashJoinExec(lkeys, rkeys, how, cond, left, right,
                                        null_safe)
    if side == "left":
        return _swapped_broadcast(left, right, lkeys, rkeys, how, cond,
                                  null_safe)
    if nparts > 1:
        env = session.shuffle_env
        # keys bind identically post-shuffle (same child schema)
        left = CpuShuffleExchangeExec(HashPartitioning(lkeys, nparts), left,
                                      shuffle_env=env)
        right = CpuShuffleExchangeExec(HashPartitioning(rkeys, nparts),
                                       right, shuffle_env=env)
    return CpuShuffledHashJoinExec(lkeys, rkeys, how, cond, left, right,
                                   null_safe)
