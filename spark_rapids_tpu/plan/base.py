"""Physical plan node base classes.

Reference: ``GpuExec.scala`` (trait GpuExec :214 internalDoExecuteColumnar)
and Spark's SparkPlan.  Every exec produces an iterator of columnar batches
per partition:

- device execs ("Tpu*Exec") yield ``ColumnarBatch`` (jax arrays, padded)
- host execs (the CPU fallback engine) yield ``HostColumnarBatch`` (arrow)

Partitioning model: a plan executes as ``num_partitions`` independent
partitions (Spark task analog); sources define the count, narrow ops
preserve it, exchanges change it (shuffle layer).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Iterator, List, Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch

#: process-wide task-thread count for execute_all; set from
#: ``spark.rapids.tpu.taskParallelism`` each time TpuOverrides.apply prepares
#: a plan (execs carry no conf).  0 = auto (min(4, cpu_count)).
_task_parallelism = 0
#: unique task ids across the process — partition indexes would collide when
#: independent plans execute concurrently (semaphore/metrics key on this)
_task_ids = itertools.count(1)

#: monotone execution-epoch source: every prepared action (and every
#: speculation replay / plan-cache re-execution) draws a fresh epoch and
#: stamps it onto the plan's per-execution caches (CTE materialization),
#: so batches cached by a previous action are never replayed stale
_execution_epochs = itertools.count(1)


def next_execution_epoch() -> int:
    return next(_execution_epochs)


def set_task_parallelism(n: int) -> None:
    global _task_parallelism
    _task_parallelism = n


#: per-task OOM injection mode from spark.rapids.sql.test.injectRetryOOM:
#: 'false' | 'true' (first tracked alloc of each task) | '<n>' (n-th)
_task_oom_injection = "false"


def set_task_oom_injection(mode: str) -> None:
    global _task_oom_injection
    _task_oom_injection = (mode or "false").strip().lower()


def _arm_task_injection() -> None:
    from spark_rapids_tpu.memory.retry import force_retry_oom
    mode = _task_oom_injection
    if mode in ("", "false"):
        # disarm: an injection left unconsumed by the previous task on
        # this pooled thread must not fire in an unrelated query
        force_retry_oom(0)
        return
    if mode == "true":
        force_retry_oom(1, framed_only=True)
    else:
        try:
            nth = int(mode)
        except ValueError:
            force_retry_oom(0)
            return
        force_retry_oom(1, skip=max(0, nth - 1), framed_only=True)


def effective_task_parallelism() -> int:
    import os
    n = _task_parallelism
    if n <= 0:
        n = min(4, os.cpu_count() or 1)
    return n


#: task-retry policy from spark.rapids.task.* (set by TpuOverrides.apply,
#: same module-global pattern as _task_parallelism)
_task_max_failures = 2
_breaker_threshold = 3


def set_task_retry_policy(max_failures: int, breaker_threshold: int) -> None:
    global _task_max_failures, _breaker_threshold
    _task_max_failures = max(1, int(max_failures))
    _breaker_threshold = max(0, int(breaker_threshold))


def _is_retryable(exc: BaseException) -> bool:
    """Failures worth re-attempting: transient data-movement errors and
    injected chaos.  Logic errors (TypeError, AssertionError, ...) are
    not — re-running deterministic breakage just hides it."""
    from spark_rapids_tpu.aux.faults import InjectedFault
    return isinstance(exc, (InjectedFault, ConnectionError, TimeoutError))


def _should_retry_task(e: BaseException, produced: int, attempts: int,
                       p: int, breaker=None, stop_on_trip: bool = False,
                       stop=None):
    """THE task-retry decision (shared by the serial/degraded iterator and
    the pooled driver so classification, budget, breaker accounting and
    the taskRetry emit cannot drift apart).  Returns (retry, zero_yield_
    retryable); emits taskRetry when retry is granted."""
    retryable = _is_retryable(e) and produced == 0
    if retryable and breaker is not None:
        breaker.record_failure()
    retry = (retryable and attempts < _task_max_failures
             and not (stop_on_trip and breaker is not None
                      and breaker.tripped)
             and not (stop is not None and stop.is_set()))
    if retry:
        from spark_rapids_tpu.aux.events import emit
        from spark_rapids_tpu.aux.faults import note_recovery
        note_recovery("task_retries")
        emit("taskRetry", pidx=p, attempt=attempts,
             error=f"{type(e).__name__}: {e}"[:160])
    return retry, retryable


def close_iter(it) -> None:
    """Explicitly closes a generator/iterator if it supports close().

    Abandoning a suspended generator leaves its cleanup to GC; the
    pipelined chains (exec/pipeline.py spools, spillable-queueing retry
    generators) need DETERMINISTIC close propagation so early exit
    releases queued spillables and stops producer threads immediately."""
    close = getattr(it, "close", None)
    if close is None:
        return
    try:
        close()
    except Exception:   # noqa: BLE001 - cleanup must not mask the cause
        pass


@contextlib.contextmanager
def closing_source(it):
    """``with closing_source(child.execute_partition(p)) as it:`` — the
    generator-chain form of ``close_iter``: whatever exits the block
    (exhaustion, failure, or a downstream ``.close()`` arriving as
    GeneratorExit) closes the source deterministically."""
    try:
        yield it
    finally:
        close_iter(it)


def _task_attempts_iter(task_fn, p: int, breaker=None):
    """Drives ``task_fn(p)`` with task-level retry: a retryable failure
    that strikes BEFORE the first item is yielded re-runs the task (fresh
    task id, fresh injection arming) up to the attempt budget; a failure
    after partial output cannot re-run without duplicating rows and
    propagates.  Each retryable failure feeds the stage breaker.  Used
    for serial stages AND as the degraded inline runner after a breaker
    trip (hence no stop_on_trip: the degraded path must keep retrying)."""
    attempts = 0
    while True:
        produced = 0
        it = task_fn(p)
        try:
            for item in it:
                produced += 1
                yield item
            return
        except GeneratorExit:
            raise
        except BaseException as e:
            attempts += 1
            retry, _ = _should_retry_task(e, produced, attempts, p,
                                          breaker)
            if not retry:
                raise
        finally:
            # runs on exhaustion (no-op), on failure, and when the
            # consumer closes THIS generator at the yield (GeneratorExit):
            # the task's chain tears down deterministically either way
            close_iter(it)


class Exec:
    """Physical operator."""

    #: True when this exec runs on the device and yields ColumnarBatch
    is_device = False

    def __init__(self, children: Sequence["Exec"] = ()):
        self.children: List[Exec] = list(children)
        self.metrics = {}
        # guards lazily-materialized per-exec state (shuffle stores,
        # broadcast build sides) against concurrent partition tasks;
        # with_children's copy.copy shares it, which only over-serializes
        self._exec_lock = threading.Lock()

    # -- static shape -------------------------------------------------------
    @property
    def schema(self) -> T.StructType:
        raise NotImplementedError

    @property
    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions
        return 1

    @property
    def name(self) -> str:
        return type(self).__name__

    def node_desc(self) -> str:
        return self.name

    # -- execution ----------------------------------------------------------
    def execute_partition(self, pidx: int):
        """Yields batches for one partition (host or device per is_device)."""
        raise NotImplementedError

    def execute_all(self):
        """Drives every partition as a task.  With taskParallelism > 1 a
        bounded thread pool runs partitions concurrently — host work
        (shuffle ser/deser, I/O, arrow) overlaps device dispatch, and the
        TpuSemaphore bounds device admission (reference: the executor's
        task slots + GpuSemaphore, GpuSemaphore.scala:51-120;
        RapidsShuffleInternalManagerBase.scala:120-218 thread pools).
        Batches are yielded in partition order regardless of completion
        order, so results stay deterministic."""
        yield from iter_partition_tasks(
            lambda p: run_task(self, p), self.num_partitions)

    def collect_host(self) -> HostColumnarBatch:
        """Gathers every partition to one host batch (driver collect).
        ``dl_spec_rows`` is stamped on the executed root by
        ``TpuOverrides.apply`` (spark.rapids.sql.collect.speculativeRows)
        so a fully-device plan — no DeviceToHost boundary above it —
        still honors the conf on this final download."""
        from spark_rapids_tpu.columnar.batch import (batch_from_pydict,
                                                     concat_host_batches)
        spec_rows = getattr(self, "dl_spec_rows", None)
        out = []
        for b in self.execute_all():
            if isinstance(b, ColumnarBatch):
                b = b.to_host(spec_rows=spec_rows)
            out.append(b)
        if not out:
            import pyarrow as pa
            empty = pa.table({f.name: pa.array([], type=T.to_arrow(f.data_type))
                              for f in self.schema})
            from spark_rapids_tpu.columnar.batch import batch_from_arrow
            return batch_from_arrow(empty)
        return concat_host_batches(out)

    # -- tree utilities -----------------------------------------------------
    def with_children(self, children: List["Exec"]) -> "Exec":
        import copy
        node = copy.copy(self)
        node.children = list(children)
        return node

    def transform_up(self, fn) -> "Exec":
        node = self.with_children([c.transform_up(fn) for c in self.children])
        return fn(node)

    def collect_nodes(self, pred=lambda n: True) -> List["Exec"]:
        out = []
        for c in self.children:
            out.extend(c.collect_nodes(pred))
        if pred(self):
            out.append(self)
        return out

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        mark = "*" if self.is_device else " "
        lines = [f"{pad}{mark}{self.node_desc()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def __repr__(self):
        return self.node_desc()


def run_task(plan: "Exec", pidx: int):
    """Drives one partition as a task: a fresh task id + metrics bind to the
    executing thread for the duration, and the device semaphore (acquired by
    any device section during execution) is fully released at completion,
    like the reference's task-completion listener (GpuSemaphore.scala:51-120
    + RmmSpark thread-to-task registration)."""
    yield from run_task_iter(plan.execute_partition, pidx)


def run_task_iter(gen_fn, pidx: int):
    """``run_task`` semantics over an arbitrary per-partition generator —
    exchange map sides run through this so each map partition is a real
    task (own id, metrics, semaphore release at completion).  The task
    registers with the resource arbiter for its duration (the thread-state
    registry behind blocking allocation and the hung-query watchdog) and
    heartbeats once per yielded batch — the watchdog's last-progress
    signal."""
    from spark_rapids_tpu.memory.arbiter import get_arbiter
    from spark_rapids_tpu.memory.device_manager import get_runtime
    from spark_rapids_tpu.memory.metrics import task_scope
    task_id = next(_task_ids)
    rt = get_runtime()
    arb = get_arbiter()
    with task_scope(task_id, rt.metrics if rt is not None else None):
        # conf-driven per-task fault injection
        # (spark.rapids.sql.test.injectRetryOOM; reference
        # RapidsConf.scala:1541 TEST_RETRY_OOM_INJECTION_MODE)
        _arm_task_injection()
        # chaos layer: spark.rapids.chaos.task.run faults the task at
        # start — before any output — so the retry path stays lossless
        from spark_rapids_tpu.aux.faults import maybe_fire
        maybe_fire("task.run")
        arb.register_task(task_id)
        from spark_rapids_tpu.aux.tracing import span_pulls
        it = span_pulls("task.run", gen_fn(pidx), partition=pidx)
        try:
            for item in it:
                arb.note_progress(task_id)
                yield item
        finally:
            # explicit close replaces the `yield from` delegation so
            # GeneratorExit/teardown still propagates into the chain
            close_iter(it)
            arb.deregister_task(task_id)
            rt = get_runtime()
            if rt is not None:
                rt.semaphore.release_all(task_id)


def release_semaphore_for_wait() -> None:
    """Releases the current task's device admission before a blocking wait
    on other tasks' progress (exchange materialization, broadcast build) —
    otherwise tasks holding every permit can all block on workers that need
    one.  Device sections re-acquire lazily afterwards.  Reference: the
    semaphore is released while a task blocks on a shuffle fetch
    (GpuShuffleExchangeExecBase / RapidsCachingReader wait paths)."""
    from spark_rapids_tpu.memory.device_manager import get_runtime
    rt = get_runtime()
    if rt is not None:
        rt.semaphore.release_all()


class _PartitionError:
    __slots__ = ("exc", "can_rerun")

    def __init__(self, exc: BaseException, can_rerun: bool = False):
        self.exc = exc
        #: True when the task failed retryably with ZERO items delivered —
        #: the consumer may re-run it inline (degraded mode) without
        #: duplicating output
        self.can_rerun = can_rerun


_DONE = object()


def iter_partition_tasks(task_fn, n: int, workers: Optional[int] = None):
    """Runs ``task_fn(p) -> iterator`` for ``p in range(n)`` and yields every
    produced item in partition order.

    With effective parallelism > 1 this is a windowed producer/consumer:
    each partition's items drain into its own bounded queue (caps buffered
    batches per partition), so partition p's items are being yielded while
    partitions p+1..p+workers-1 are already producing.  A stop event
    unblocks producers if the consumer abandons the generator (e.g. a
    short-circuiting limit).  Used by ``Exec.execute_all`` and by exchange
    map sides (the reference's task slots / multithreaded shuffle writer
    pools, RapidsShuffleInternalManagerBase.scala:120-218)."""
    from spark_rapids_tpu.aux.faults import CircuitBreaker
    if workers is None:
        workers = effective_task_parallelism()
    workers = min(workers, n)
    if workers <= 1:
        for p in range(n):
            yield from _task_attempts_iter(task_fn, p)
        return

    import queue as qmod
    from concurrent.futures import ThreadPoolExecutor

    qs = [qmod.Queue(maxsize=4) for _ in range(n)]
    stop = threading.Event()
    #: stage-scoped: repeated retryable task failures trip it, degrading
    #: the remainder of the stage to single-threaded inline execution in
    #: the consumer thread instead of failing the query
    breaker = CircuitBreaker(_breaker_threshold, name=f"stage-{n}p")

    def put(q, item) -> bool:
        released = False
        while True:
            try:
                q.put(item, timeout=0.05)
                return True
            except qmod.Full:
                if stop.is_set():
                    return False
                if not released:
                    # waiting on backpressure must not hold device
                    # admission: tasks parked on full queues would
                    # otherwise starve the partition the consumer is
                    # draining (permits re-acquire lazily at the next
                    # device section)
                    release_semaphore_for_wait()
                    released = True

    def drive(p: int) -> None:
        q = qs[p]
        attempts = 0
        try:
            while True:
                produced = 0
                it = task_fn(p)
                try:
                    for b in it:
                        produced += 1
                        if stop.is_set() or not put(q, b):
                            return
                    return
                except BaseException as e:  # propagated to the consumer
                    attempts += 1
                    retry, retryable = _should_retry_task(
                        e, produced, attempts, p, breaker,
                        stop_on_trip=True, stop=stop)
                    if retry:
                        continue
                    put(q, _PartitionError(e, can_rerun=retryable))
                    return
                finally:
                    # a consumer that abandoned the stage (stop set) must
                    # not leave this task's chain to GC: close releases
                    # queued spillables / prefetch threads upstream NOW
                    close_iter(it)
        finally:
            put(q, _DONE)

    # each task runs inside a COPY of the submitting thread's context so
    # contextvars (the speculation scope of the owning collect) propagate
    # to pool threads — two concurrent collects must not mix their
    # overflow flags
    import contextvars
    ctx = contextvars.copy_context()
    pool = ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix="tpu-task")
    try:
        for p in range(n):
            pool.submit(ctx.copy().run, drive, p)
        for p in range(n):
            while True:
                item = qs[p].get()
                if item is _DONE:
                    break
                if isinstance(item, _PartitionError):
                    if item.can_rerun and breaker.tripped:
                        # degraded mode: the breaker tripped on repeated
                        # faults — run this partition inline on THIS
                        # thread (single-threaded, no pool) instead of
                        # failing the query; zero items were delivered,
                        # so the re-run cannot duplicate output
                        while qs[p].get() is not _DONE:
                            pass
                        from spark_rapids_tpu.aux.events import emit
                        from spark_rapids_tpu.aux.faults import \
                            note_recovery
                        note_recovery("tasks_degraded")
                        emit("taskDegraded", pidx=p,
                             error=f"{type(item.exc).__name__}: "
                                   f"{item.exc}"[:160])
                        yield from _task_attempts_iter(task_fn, p,
                                                       breaker)
                        break
                    raise item.exc
                yield item
    finally:
        stop.set()
        for q in qs:  # unblock producers stuck on a full queue
            try:
                while True:
                    q.get_nowait()
            except qmod.Empty:
                pass
        pool.shutdown(wait=True, cancel_futures=True)


class LeafExec(Exec):
    def __init__(self):
        super().__init__([])


class UnaryExec(Exec):
    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def child(self) -> Exec:
        return self.children[0]

    @property
    def schema(self) -> T.StructType:
        return self.child.schema


class BinaryExec(Exec):
    def __init__(self, left: Exec, right: Exec):
        super().__init__([left, right])

    @property
    def left(self) -> Exec:
        return self.children[0]

    @property
    def right(self) -> Exec:
        return self.children[1]


def is_device_exec(node: Exec) -> bool:
    return node.is_device
