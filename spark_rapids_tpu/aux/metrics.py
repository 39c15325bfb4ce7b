"""Operator metrics.

Reference: GpuExec.scala:36-120 — ``GpuMetric`` wrappers over SQLMetric
with levels ESSENTIAL/MODERATE/DEBUG selected by
``spark.rapids.sql.metrics.level``; standard names (opTime,
numOutputRows, numOutputBatches, ...).

Instrumentation wraps each exec's ``execute_partition`` with counters,
a wall-clock timer and — when a ``QueryExecution`` is active — a
per-partition child span so layer events attribute to the operator that
triggered them.  Every pull runs under that span and writes
``srt.exec.<node name>`` into the profiler's trace
(``tracing.partition_pull``).
``collect_metrics`` renders the tree's totals."""

from __future__ import annotations

import enum
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux import tracing as _tracing
from spark_rapids_tpu.plan.base import Exec


class MetricLevel(enum.IntEnum):
    ESSENTIAL = 0
    MODERATE = 1
    DEBUG = 2

    @staticmethod
    def parse(s: str) -> "MetricLevel":
        try:
            return MetricLevel[str(s).strip().upper()]
        except KeyError:
            raise ValueError(
                f"invalid metrics level {s!r}; expected one of "
                f"{', '.join(MetricLevel.__members__)}") from None


# standard metric names (reference GpuExec.scala:49-120) with their levels
STANDARD_METRICS = {
    "numOutputRows": MetricLevel.ESSENTIAL,
    "numOutputBatches": MetricLevel.MODERATE,
    "opTime": MetricLevel.MODERATE,
    "streamTime": MetricLevel.DEBUG,
}


class OpMetric:
    __slots__ = ("name", "level", "value", "pending")

    def __init__(self, name: str, level: MetricLevel):
        self.name = name
        self.level = level
        self.value = 0
        #: DeferredCounts observed before they were forced; resolved
        #: (without a sync) once the query's download forces them
        self.pending = None

    def add(self, v) -> None:
        self.value += v

    def defer(self, count) -> None:
        if self.pending is None:
            self.pending = []
        self.pending.append(count)

    def resolve(self) -> None:
        """Folds deferred counts the query has since forced into the
        value; never syncs (unforced counts stay pending)."""
        if not self.pending:
            return
        still = []
        for c in self.pending:
            if c.is_forced:
                self.value += int(c)
            else:
                still.append(c)
        self.pending = still or None

    def __repr__(self):
        return f"{self.name}={self.value}"


def _ensure_metrics(node: Exec, level: MetricLevel) -> Dict[str, OpMetric]:
    ms = {}
    for name, lv in STANDARD_METRICS.items():
        if lv <= level:
            ms[name] = OpMetric(name, lv)
    node.metrics = ms
    return ms


_END = object()


def instrument_plan(plan: Exec, level: MetricLevel) -> Exec:
    """Wraps every node's execute_partition with metric recording (the
    GpuMetric counters around internalDoExecuteColumnar), and an
    exchange's ``read_range`` beside it (the adaptive reader's way in:
    first partition, end, pieces).

    Metrics are reset first: plan rewrites shallow-copy nodes but SHARE
    the metrics dicts, so without the reset repeated actions on the same
    DataFrame accumulate across queries (the re-run staleness bug) and
    ``collect_metrics`` / ``explain(analyze=True)`` stop being per-query.
    """
    reset_metrics(plan)
    for node in plan.collect_nodes():
        if getattr(node, "_instrumented", False):
            continue
        ms = _ensure_metrics(node, level)
        if not ms:
            continue
        for method in ("execute_partition", "read_range"):
            if hasattr(node, method):
                setattr(node, method,
                        _recording(getattr(node, method), ms, node.name))
        node._instrumented = True
    return plan


def _recording(inner, ms: Dict[str, OpMetric], name: str):
    """``inner`` (a bound ``execute_partition`` or ``read_range``) with its
    rows, batches and time recorded on ``ms`` and on a partition span."""
    def wrapped(pidx, *more):
        rows = ms.get("numOutputRows")
        batches = ms.get("numOutputBatches")
        optime = ms.get("opTime")
        q = EV.active_query()
        pspan = q.start_partition(id(ms), pidx) if q is not None else None
        it = inner(pidx, *more)
        try:
            while True:
                t0 = time.perf_counter()
                with _tracing.partition_pull(q, pspan, name):
                    b = next(it, _END)
                if b is _END:
                    break
                dt = time.perf_counter() - t0
                if rows is not None:
                    # deferred device counts must not sync here; track
                    # them and fold in lazily once the query's own
                    # download forces them (resolve())
                    rc = b.row_count
                    from spark_rapids_tpu.columnar.column import \
                        DeferredCount
                    if not isinstance(rc, DeferredCount) or rc.is_forced:
                        n = int(rc)
                        rows.add(n)
                        if pspan is not None:
                            pspan.rows += n
                    else:
                        rows.defer(rc)
                if batches is not None:
                    batches.add(1)
                if optime is not None:
                    optime.add(dt)
                if pspan is not None:
                    pspan.batches += 1
                    # rows with their padding: the bucket is a host
                    # int, so no sync (host batches have none)
                    pspan.padded_rows += getattr(b, "bucket", 0)
                yield b
        finally:
            if q is not None and pspan is not None:
                q.end_partition(pspan)

    return wrapped


def reset_metrics(plan: Exec) -> None:
    """Zeroes every node's OpMetric counters so the next action reports
    per-query values (called at query start by ``instrument_plan``)."""
    for node in plan.collect_nodes():
        for m in (getattr(node, "metrics", None) or {}).values():
            m.value = 0
            m.pending = None


def collect_metrics(plan: Exec) -> List[Dict]:
    """Per-node metric snapshot (driver-side report; the reference surfaces
    these in the Spark UI via SQLMetrics)."""
    out = []
    for node in plan.collect_nodes():
        ms = getattr(node, "metrics", None) or {}
        if ms:
            for m in ms.values():
                m.resolve()
            out.append({"node": node.node_desc(),
                        **{m.name: round(m.value, 6) if
                           isinstance(m.value, float) else m.value
                           for m in ms.values()}})
    return out
