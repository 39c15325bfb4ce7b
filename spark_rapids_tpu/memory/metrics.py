"""Per-task accounting (reference: GpuTaskMetrics.scala — semaphore wait,
retry counts, spill sizes/times, max device memory, surfaced as accumulators).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Optional


@dataclasses.dataclass
class TaskMetrics:
    task_id: int = -1
    semaphore_wait_seconds: float = 0.0
    #: seconds parked in the arbiter's BLOCKED_ON_ALLOC state waiting for
    #: concurrent tasks to release memory (memory/arbiter.py)
    alloc_wait_seconds: float = 0.0
    retry_count: int = 0
    split_retry_count: int = 0
    oom_count: int = 0
    spill_count: int = 0
    spill_bytes: int = 0
    op_time_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    max_device_bytes: int = 0

    def observe_device_bytes(self, n: int) -> None:
        if n > self.max_device_bytes:
            self.max_device_bytes = n

    @contextlib.contextmanager
    def time_op(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.op_time_seconds[name] = (self.op_time_seconds.get(name, 0.0) +
                                          time.monotonic() - t0)

    def merge(self, other: "TaskMetrics") -> None:
        self.semaphore_wait_seconds += other.semaphore_wait_seconds
        self.alloc_wait_seconds += other.alloc_wait_seconds
        self.retry_count += other.retry_count
        self.split_retry_count += other.split_retry_count
        self.oom_count += other.oom_count
        self.spill_count += other.spill_count
        self.spill_bytes += other.spill_bytes
        for k, v in other.op_time_seconds.items():
            self.op_time_seconds[k] = self.op_time_seconds.get(k, 0.0) + v
        self.max_device_bytes = max(self.max_device_bytes, other.max_device_bytes)


class MetricsRegistry:
    """Aggregates finished tasks' metrics (driver-side accumulator analog)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.total = TaskMetrics()
        self.finished_tasks = 0
        self.started_tasks = 0

    def note_started(self) -> None:
        with self._lock:
            self.started_tasks += 1

    def active_count(self) -> int:
        """Tasks started but not yet reported (the resource sampler's
        active-task gauge)."""
        with self._lock:
            return max(0, self.started_tasks - self.finished_tasks)

    def report(self, m: TaskMetrics) -> None:
        with self._lock:
            self.total.merge(m)
            self.finished_tasks += 1

    def snapshot(self):
        """(totals copy, finished_tasks) under one lock."""
        with self._lock:
            s = TaskMetrics()
            s.merge(self.total)
            return s, self.finished_tasks


@contextlib.contextmanager
def task_scope(task_id: int, registry: Optional[MetricsRegistry] = None):
    """Binds a task id + metrics to the current thread for the duration of a
    task (reference: RmmSpark thread-to-task registration + onTaskCompletion
    listeners in ScalableTaskCompletion)."""
    from spark_rapids_tpu.memory.retry import task_context
    ctx = task_context()
    prev_id, prev_metrics = ctx.task_id, ctx.metrics
    ctx.task_id = task_id
    ctx.metrics = TaskMetrics(task_id=task_id)
    if registry is not None:
        registry.note_started()
    try:
        yield ctx.metrics
    finally:
        if registry is not None:
            registry.report(ctx.metrics)
        m = ctx.metrics
        from spark_rapids_tpu.aux.events import active_query, emit
        q = active_query()
        if q is not None:       # the query's summary sums its own tasks
            q.note_task(m)
        emit("taskEnd", task_id=task_id, retry_count=m.retry_count,
             split_retry_count=m.split_retry_count, oom_count=m.oom_count,
             spill_count=m.spill_count, spill_bytes=m.spill_bytes,
             semaphore_wait_s=round(m.semaphore_wait_seconds, 6),
             alloc_wait_s=round(m.alloc_wait_seconds, 6),
             max_device_bytes=m.max_device_bytes)
        # release the semaphore if the task still holds it (completion listener)
        from spark_rapids_tpu.memory.device_manager import get_runtime
        rt = get_runtime()
        if rt is not None:
            rt.semaphore.release_if_necessary(task_id)
        ctx.task_id, ctx.metrics = prev_id, prev_metrics
