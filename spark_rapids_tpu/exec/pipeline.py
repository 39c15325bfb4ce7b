"""Asynchronous pipelined execution: bounded-depth prefetch boundaries.

The engine's iterator chains are pull-based and fully synchronous: when a
fused stage asks for its next batch, the scan decodes on the host, the
transfer pays its fixed per-transfer cost (columnar/transfer.py), and
only then does the TPU kernel dispatch — at any instant two of the three
resources (host CPU, host-device link, TPU) sit idle.
Theseus (PAPERS.md) shows a device query engine's wall-clock is dominated
by exactly this data-movement serialization and wins by overlapping I/O,
transfer and compute; this module is that overlap as a plan rewrite.

``PrefetchExec`` is a transparent unary node the planner inserts at
asynchrony-profitable boundaries (``insert_pipeline_prefetch``):

- host decode feeding ``HostToDeviceExec``  (decode N+1 while N transfers)
- transfer/shuffle output feeding device compute (ship N+1 while N computes,
  exploiting JAX async dispatch before deferred counts are forced)
- device compute feeding ``DeviceToHostExec`` (compute N+1 while N downloads)

Each ``execute_partition`` spins a ``PrefetchSpool``: ONE producer thread
drains the upstream generator into a bounded queue (depth AND in-flight
bytes bounded, ``spark.rapids.pipeline.*``) while the consumer pulls from
the queue.  The spool is memory-safe and failure-safe, not just fast:

- queued DEVICE batches register with the spill framework (lowest spill
  priority — in-flight prefetch is the most evictable data in the pool)
  and therefore count against the catalog's device-store budget;
- the producer runs under the consumer task's identity, so device
  admission is ONE shared hold released by the task-completion listener;
  a producer parked on backpressure keeps it (its consumer sibling is
  the thread draining the queue, so the task keeps progressing), which
  keeps ``concurrentGpuTasks`` honest while staying deadlock-free;
- a producer exception re-raises at the consumer with the ORIGINAL
  exception object (lineage intact), before any item was delivered when
  it struck before the first yield — so PR 3's task-retry/rerun machinery
  classifies and recovers it unchanged (fault point ``pipeline.prefetch``
  exercises exactly this path);
- consumer ``.close()`` (a limit short-circuiting, an abandoned fetch)
  stops the producer, closes every queued spillable, closes the upstream
  generator IN the producer thread, and joins it — early exit can neither
  leak spillables nor strand threads.

Stall-time and queue-depth metrics flow to the event bus
(``pipelineSpool`` events) and into the node's OpMetrics so
``explain(analyze=True)`` shows measured overlap per boundary; a
process-wide ledger (``pipeline_stats``) sums them over every spool the
process closed.
"""

from __future__ import annotations

import collections
import contextvars
import threading
import time
import weakref
from typing import Optional

from spark_rapids_tpu.plan.base import (Exec, UnaryExec,
                                        release_semaphore_for_wait)

#: conf-driven (plan/overrides.apply): spark.rapids.pipeline.*
PIPELINE_ENABLED = True
PIPELINE_DEPTH = 2
PIPELINE_MAX_BYTES = 256 << 20

_DONE = object()


class _SpoolError:
    """Producer-side failure in transit to the consumer (the original
    exception object travels so type/lineage survive re-raise)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


# ---------------------------------------------------------------------------
# process-wide ledger
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()


def _zero_stats() -> dict:
    return {"spools": 0, "batches": 0, "bytes": 0,
            "producer_busy_s": 0.0, "producer_stall_s": 0.0,
            "consumer_stall_s": 0.0, "peak_depth": 0}


_STATS = _zero_stats()


def note_spool(spool: "PrefetchSpool") -> None:
    with _STATS_LOCK:
        _STATS["spools"] += 1
        _STATS["batches"] += spool.produced
        _STATS["bytes"] += spool.bytes_total
        _STATS["producer_busy_s"] += spool.producer_busy_s
        _STATS["producer_stall_s"] += spool.producer_stall_s
        _STATS["consumer_stall_s"] += spool.consumer_stall_s
        _STATS["peak_depth"] = max(_STATS["peak_depth"], spool.peak_depth)


def pipeline_stats() -> dict:
    """Snapshot with the derived overlap ratio: the fraction of upstream
    production time hidden from the consumer.  Fully serial execution has
    the consumer waiting out every producer second (ratio 0); perfect
    overlap has the consumer never waiting (ratio 1)."""
    with _STATS_LOCK:
        out = dict(_STATS)
    busy = out["producer_busy_s"]
    out["overlap_ratio"] = round(
        max(0.0, 1.0 - out["consumer_stall_s"] / busy), 4) if busy > 0 \
        else 0.0
    for k in ("producer_busy_s", "producer_stall_s", "consumer_stall_s"):
        out[k] = round(out[k], 6)
    return out


def reset_pipeline_stats() -> None:
    global _STATS
    with _STATS_LOCK:
        _STATS = _zero_stats()


#: live (unfinished) spools, for the resource sampler's point-in-time
#: queue-depth gauge; weak so a dropped spool never leaks through here
_LIVE_SPOOLS: "weakref.WeakSet" = weakref.WeakSet()


def live_spool_stats() -> dict:
    """Read-only snapshot of in-flight prefetch spools (sampler gauge).
    Depth reads race the producers by design — a sample is a sample."""
    spools = 0
    queued = 0
    queued_bytes = 0
    for s in list(_LIVE_SPOOLS):
        if s._finished:
            continue
        spools += 1
        queued += s._depth
        queued_bytes += s._bytes
    return {"spools": spools, "queued_batches": queued,
            "queued_bytes": queued_bytes}


# ---------------------------------------------------------------------------
# the spool
# ---------------------------------------------------------------------------

class PrefetchSpool:
    """Bounded producer/consumer spool over one upstream generator.

    The producer thread starts lazily at the first consumer pull (plan
    setup must not spawn threads) inside a COPY of the consumer's context
    (the active QueryExecution and speculation scope propagate, exactly
    like the task pool's ``ctx.copy().run``) and under the consumer
    task's id/metrics, so semaphore holds and pressure events attribute
    to — and are released with — the owning task.
    """

    #: contract flag the runtime plan verifier (plan/verify.py,
    #: ``spark.rapids.debug.planCheck``) asserts: ``_wrap`` registers
    #: every queued DEVICE batch with the spill framework (owned=False,
    #: lowest priority).  A refactor that drops the registration must
    #: flip this — and thereby fail every armed run — instead of
    #: silently pinning unevictable device memory in spool queues.
    QUEUED_DEVICE_BATCHES_SPILLABLE = True

    def __init__(self, source_fn, depth: int, max_bytes: int,
                 boundary: str):
        self._source_fn = source_fn
        self.depth = max(1, int(depth))
        self.max_bytes = max(1, int(max_bytes))
        self.boundary = boundary
        self._q: collections.deque = collections.deque()
        from spark_rapids_tpu.aux.lockorder import tracked_condition
        self._cond = tracked_condition("spool")
        self._depth = 0
        self._bytes = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._finished = False
        # stats
        self.produced = 0
        self.bytes_total = 0
        self.producer_busy_s = 0.0
        self.producer_stall_s = 0.0
        self.consumer_stall_s = 0.0
        self.peak_depth = 0
        # consumer task identity, adopted by the producer thread
        from spark_rapids_tpu.memory.retry import task_context
        tc = task_context()
        self._task_id = tc.task_id
        self._task_metrics = tc.metrics
        _LIVE_SPOOLS.add(self)

    # -- producer ------------------------------------------------------------
    def _start(self) -> None:
        ctx = contextvars.copy_context()
        t = threading.Thread(target=ctx.run, args=(self._produce,),
                             name=f"tpu-prefetch-{self.boundary}",
                             daemon=True)
        self._thread = t
        t.start()

    def _wrap(self, item):
        """(payload, spillable, nbytes): a device batch registers with the
        catalog so the spill framework can move it (and its bytes count
        against the device-store budget); a registration that itself hits
        pool pressure falls back to the raw batch — prefetch must relieve
        pressure, never amplify it."""
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        if isinstance(item, ColumnarBatch) and item.columns:
            nb = item.sized_nbytes()
            from spark_rapids_tpu.memory.device_manager import get_runtime
            if get_runtime() is not None:
                from spark_rapids_tpu.memory.catalog import SpillPriority
                from spark_rapids_tpu.memory.retry import RetryOOM
                from spark_rapids_tpu.memory.spillable import \
                    SpillableColumnarBatch
                try:
                    # owned=False: the arrays may be shared with upstream
                    # caches and are handed straight to the consumer — the
                    # catalog may spill (copy out + drop ref) but never
                    # .delete() them
                    spill = SpillableColumnarBatch.from_device(
                        item, priority=SpillPriority.INPUT_FROM_SHUFFLE,
                        owned=False)
                    return (None, spill, nb)
                except RetryOOM:
                    pass
            return (item, None, nb)
        nbf = getattr(item, "nbytes", None)
        return (item, None, nbf() if callable(nbf) else 0)

    @staticmethod
    def _close_entry(entry) -> None:
        spill = entry[1]
        if spill is not None:
            try:
                spill.close()
            except Exception:   # noqa: BLE001 - cleanup must not mask
                pass

    def _produce(self) -> None:
        # adopt the consumer task's identity: semaphore acquires in this
        # thread key to the task and release with it (run_task's finally),
        # and the arbiter tracks this thread under the task so the
        # deadlock detector sees the task's FULL thread set
        from spark_rapids_tpu.memory.arbiter import get_arbiter
        from spark_rapids_tpu.memory.retry import task_context
        tc = task_context()
        tc.task_id = self._task_id
        tc.metrics = self._task_metrics
        arb = get_arbiter()
        adopted = arb.adopt_thread(self._task_id)
        src = None
        try:
            from spark_rapids_tpu.aux.faults import maybe_fire
            maybe_fire("pipeline.prefetch")
            src = self._source_fn()
            while not self._stop:
                t0 = time.monotonic()
                try:
                    item = next(src)
                except StopIteration:
                    break
                self.producer_busy_s += time.monotonic() - t0
                entry = self._wrap(item)
                if not self._put(entry):
                    self._close_entry(entry)
                    break
                arb.note_progress(self._task_id)
        except BaseException as e:   # noqa: BLE001 - re-raised by consumer
            with self._cond:
                self._q.append(_SpoolError(e))
                self._cond.notify_all()
        finally:
            if adopted:
                arb.drop_thread(self._task_id)
            if src is not None:
                # the producer owns the upstream generator: closing it HERE
                # (never from the consumer thread, which would race a
                # running frame) propagates early exit all the way up
                try:
                    src.close()
                except BaseException:   # noqa: BLE001
                    pass
            if self._task_id is None:
                # no owning task: semaphore holds acquired under this
                # thread's identity have no completion listener to release
                # them — drop them now
                from spark_rapids_tpu.memory.device_manager import \
                    get_runtime
                rt = get_runtime()
                if rt is not None:
                    rt.semaphore.release_all()
            with self._cond:
                self._q.append(_DONE)
                self._cond.notify_all()

    def _put(self, entry) -> bool:
        from spark_rapids_tpu.memory.arbiter import TaskState, get_arbiter
        arb = get_arbiter()
        nb = entry[2]
        with self._cond:
            # admit at least one item regardless of its size, else a
            # batch larger than the byte budget would deadlock the spool.
            # NO semaphore release while backpressured: the device hold
            # is keyed by the task id this producer SHARES with its
            # consumer, and that consumer is the thread draining this
            # very queue — the task keeps progressing, and a whole-task
            # release would strip admission from a sibling mid-kernel
            # (over-admitting past concurrentGpuTasks)
            t0 = arb.wait_cancellable(
                self._cond,
                lambda: not self._stop and (
                    self._depth >= self.depth or
                    (self._depth > 0
                     and self._bytes + nb > self.max_bytes)),
                TaskState.BLOCKED_ON_SPOOL, slice_s=0.1,
                task_id=self._task_id)
            if t0 is not None:
                self.producer_stall_s += time.monotonic() - t0
            if self._stop:
                return False
            self._q.append(entry)
            self._depth += 1
            self._bytes += nb
            self.produced += 1
            self.bytes_total += nb
            self.peak_depth = max(self.peak_depth, self._depth)
            self._cond.notify_all()
            return True

    # -- consumer ------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        from spark_rapids_tpu.memory.arbiter import TaskState, get_arbiter
        arb = get_arbiter()
        if self._thread is None:
            self._start()
        with self._cond:
            def _on_first_wait():
                if self._task_id is None:
                    # untasked caller (direct-exec tests): the producer
                    # acquires under its OWN thread identity and could
                    # block on this thread's hold — drop it while
                    # waiting.  Tasked callers share one hold with the
                    # producer, so waiting with it held is deadlock-free
                    # and keeps admission honest.
                    release_semaphore_for_wait()

            # waiting on our own producer: a tracked blocked state (the
            # producer may itself be parked on an allocation — the task
            # is then FULLY blocked and must count toward deadlock
            # detection)
            t0 = arb.wait_cancellable(
                self._cond, lambda: not self._q,
                TaskState.BLOCKED_ON_SPOOL, slice_s=0.1,
                task_id=self._task_id, on_first_wait=_on_first_wait)
            if t0 is not None:
                self.consumer_stall_s += time.monotonic() - t0
            entry = self._q.popleft()
            if entry is _DONE:
                self._q.append(_DONE)   # repeated next() stays terminal
                self._finish()
                raise StopIteration
            if isinstance(entry, _SpoolError):
                exc = entry.exc
            else:
                exc = None
                self._depth -= 1
                self._bytes -= entry[2]
                self._cond.notify_all()
        if exc is not None:
            self._finish()
            raise exc
        payload, spill, _nb = entry
        if spill is not None:
            try:
                payload = spill.get_batch()
            finally:
                spill.close()
        arb.note_progress(self._task_id)    # spool handoff = task progress
        self._reacquire_admission(payload)
        return payload

    def _reacquire_admission(self, payload) -> None:
        """Dequeue is the owning task's device-section boundary: admission
        the producer legitimately dropped while blocked in an upstream
        wait (the exchange releases before materializing so map tasks can
        run) is re-acquired HERE, closing the over-admission window at
        the next batch instead of leaving the task computing unadmitted
        for its remainder.  Only inside a real task — its completion
        listener releases the hold; an untasked caller (direct-exec
        tests) must not pin a permit under a thread identity nothing
        releases."""
        if self._task_id is None:
            return
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        if not isinstance(payload, ColumnarBatch):
            return
        from spark_rapids_tpu.memory.device_manager import get_runtime
        rt = get_runtime()
        if rt is not None:
            rt.semaphore.acquire_if_necessary()

    def close(self) -> None:
        """Idempotent early-exit teardown: stop the producer, release every
        queued spillable, join the thread.  Safe to call after normal
        exhaustion (everything is already drained)."""
        with self._cond:
            self._stop = True
            pending = [e for e in self._q
                       if e is not _DONE and not isinstance(e, _SpoolError)]
            self._q.clear()
            self._depth = 0
            self._bytes = 0
            self._cond.notify_all()
        for e in pending:
            self._close_entry(e)
        t = self._thread
        if t is not None and t is not threading.current_thread():
            # the producer may be mid-pull on a slow upstream; it checks
            # the stop flag right after and exits.  The join bound keeps a
            # wedged upstream from hanging the consumer's close.
            t.join(timeout=10.0)
        self._finish()

    def _finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        note_spool(self)
        from spark_rapids_tpu.aux.events import emit
        emit("pipelineSpool", boundary=self.boundary,
             batches=self.produced,
             producer_busy_s=round(self.producer_busy_s, 6),
             producer_stall_s=round(self.producer_stall_s, 6),
             consumer_stall_s=round(self.consumer_stall_s, 6),
             peak_depth=self.peak_depth)


# ---------------------------------------------------------------------------
# the exec + planner pass
# ---------------------------------------------------------------------------

class PrefetchExec(UnaryExec):
    """Transparent pipelining boundary: schema/partitioning/device-ness all
    mirror the child; execution interposes a PrefetchSpool."""

    def __init__(self, child: Exec, boundary: str,
                 depth: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        super().__init__(child)
        self.boundary = boundary
        self.depth = depth
        self.max_bytes = max_bytes
        # instance attr shadows the class default so transitions/markers
        # see the wrapped tier
        self.is_device = child.is_device

    def execute_partition(self, pidx):
        depth = self.depth if self.depth is not None else PIPELINE_DEPTH
        mb = self.max_bytes if self.max_bytes is not None \
            else PIPELINE_MAX_BYTES
        spool = PrefetchSpool(
            lambda: self.child.execute_partition(pidx), depth, mb,
            self.boundary)
        try:
            # PEP 380: closing this generator close()s the spool via the
            # delegation protocol; the finally covers error paths too
            yield from spool
        finally:
            spool.close()
            self._note_metrics(spool)

    def _note_metrics(self, spool: PrefetchSpool) -> None:
        """Folds spool stats into this node's OpMetrics so the span tree
        (explain(analyze=True)) shows per-boundary overlap."""
        ms = getattr(self, "metrics", None)
        if not isinstance(ms, dict):
            return
        from spark_rapids_tpu.aux.metrics import MetricLevel, OpMetric

        def metric(name: str) -> OpMetric:
            m = ms.get(name)
            if m is None:
                m = ms[name] = OpMetric(name, MetricLevel.MODERATE)
            return m

        metric("producerStallTime").add(round(spool.producer_stall_s, 6))
        metric("consumerStallTime").add(round(spool.consumer_stall_s, 6))
        pk = metric("peakQueueDepth")
        pk.value = max(pk.value, spool.peak_depth)

    def node_desc(self):
        d = self.depth if self.depth is not None else PIPELINE_DEPTH
        return f"Prefetch[{self.boundary}, depth={d}]"


def insert_pipeline_prefetch(plan: Exec) -> Exec:
    """Planner pass (runs LAST, after reuse/adaptive): wraps the
    asynchrony-profitable boundaries in PrefetchExec.  Identity-memoized —
    a node shared by several parents (ReuseExchange, CTE collapse) must
    map to ONE rewritten node or the sharing silently splits into
    per-parent copies that each re-materialize their shuffle."""
    from spark_rapids_tpu.exec.adaptive import AdaptiveShuffleReaderExec
    from spark_rapids_tpu.exec.basic import (CpuInMemoryScanExec,
                                             DeviceToHostExec,
                                             HostToDeviceExec,
                                             TpuCoalesceBatchesExec)
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec

    def boundary_for(c: Exec) -> Optional[str]:
        if isinstance(c, (HostToDeviceExec, TpuCoalesceBatchesExec)):
            return "transfer"
        if isinstance(c, (CpuShuffleExchangeExec,
                          AdaptiveShuffleReaderExec)):
            return "shuffle"
        if isinstance(c, CpuInMemoryScanExec) and c.is_device:
            # device-resident scan: the producer pays the (first-action)
            # upload and cache assembly while the consumer computes
            return "upload"
        return None

    memo: dict = {}

    def visit(node: Exec) -> Exec:
        hit = memo.get(id(node))
        if hit is not None:
            return hit
        kids = [visit(c) for c in node.children]
        if isinstance(node, PrefetchExec):
            pass   # already a boundary: never stack spools
        elif isinstance(node, HostToDeviceExec):
            if not isinstance(kids[0], PrefetchExec):
                kids = [PrefetchExec(kids[0], "decode")]
        elif isinstance(node, DeviceToHostExec):
            if not isinstance(kids[0], PrefetchExec):
                kids = [PrefetchExec(kids[0], "d2h")]
        elif node.is_device and not isinstance(
                node, (TpuCoalesceBatchesExec, AdaptiveShuffleReaderExec)):
            # (the coalescer and the adaptive reader introspect their
            # direct child — the spool goes ABOVE them, never inside)
            kids = [PrefetchExec(c, b)
                    if not isinstance(c, PrefetchExec)
                    and (b := boundary_for(c)) is not None else c
                    for c in kids]
        if kids != node.children:
            # mutate IN PLACE (like instrument_plan): this pass runs on
            # the per-action executed tree, and a with_children copy here
            # would split identities other passes pinned — the adaptive
            # readers' coordinated specs reference the in-tree exchange
            # instances, and reuse/CTE sharing is by identity
            node.children = kids
        memo[id(node)] = node
        return node

    return visit(plan)
