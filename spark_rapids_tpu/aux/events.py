"""Query event bus + pluggable sinks.

Reference: the Spark event log (SparkListenerEvent JSON lines consumed by
the history server) crossed with the plugin's accumulators — the reference
surfaces semaphore/retry/spill via GpuTaskMetrics and NVTX; here every
layer emits a typed ``Event`` through one process-wide bus:

- ``emit(kind, **payload)`` is the single hook the memory / shuffle /
  task layers call.  It is zero-cost when nothing listens: one contextvar
  read when no ``QueryExecution`` is active and no global sink is
  registered.
- Events route to the active query's ring buffer + sinks (the query id
  and span id are stamped there), or to process-global sinks for
  daemon-thread emitters that run outside any query (heartbeats,
  shuffle workers, the resource sampler).

Sinks: ``JsonlEventLogSink`` (the event-log file analog, conf
``spark.rapids.sql.eventLog.path``, with size-based rotation and optional
gzip compression), ``RingBufferSink`` (in-memory, for tests and
``explain(analyze=True)``), and ``render_prometheus()`` — a text
exposition of the registry's gauges/counters for scrapers.

Every ``emit(kind=...)`` call site in the package must use a kind from
``EVENT_KINDS`` (pinned by a tier-1 ast test) so the offline reader
(``spark_rapids_tpu.tools``) can rely on known schemas.
"""

from __future__ import annotations

import atexit
import collections
import contextvars
import dataclasses
import gzip
import json
import os
import threading
import time
import weakref
from typing import Dict, List, Optional

#: v1 = PR 1 envelope (event/query_id/span_id/ts).  v2 adds the offline
#: reader's structural fields: spanMetrics rows carry parent_id / depth /
#: start_s / end_s / partitions, queryStart carries the non-default conf
#: snapshot, and files open with an ``eventLogHeader`` line.  v3 adds the
#: compiled-program audit ledger: ``stageProgram`` rows (one per built
#: executable — jaxpr signatures, const shapes/fingerprints, arg
#: signature, flops/bytes, key provenance) and ``planInvariantViolation``
#: rows from the runtime plan verifier.  v4 adds the host-transition
#: ledger: ``hostTransition`` rows (one per packed H2D/D2H batch
#: transfer — direction, bytes, encoding kinds, duration) and
#: ``deviceSync`` rows (one per non-transfer blocking sync — site,
#: duration) from aux/transitions.py.  The reader (tools/reader.py)
#: accepts all four.
EVENT_SCHEMA_VERSION = 4

#: stamped on events emitted outside any query / span scope
NO_QUERY = -1
NO_SPAN = -1

#: THE event-kind catalog: every ``emit(kind=...)`` / ``record_event``
#: call site in the package uses one of these (tier-1 ast test), so the
#: offline reader can rely on a closed vocabulary.  Grouped by emitter.
EVENT_KINDS = frozenset({
    # tracing lifecycle (aux/tracing.py)
    "queryStart", "queryEnd", "spanMetrics",
    # event-log file framing (this module)
    "eventLogHeader",
    # memory layer (memory/catalog.py, retry.py, semaphore.py, metrics.py)
    "spill", "unspill", "oom", "retryOOM", "splitRetry",
    "semaphoreAcquired", "taskEnd",
    # cooperative memory arbitration + hung-query watchdog
    # (memory/arbiter.py)
    "threadBlocked", "deadlockBreak", "watchdogDump", "taskCancelled",
    # task runner (plan/base.py)
    "taskRetry", "taskDegraded",
    # pipelined execution (exec/pipeline.py)
    "pipelineSpool",
    # stage compiler (exec/stage_compiler.py); stageProgram is the
    # per-executable audit ledger row (schema v3, tools/audit)
    "stageCompile", "stageProgram",
    # runtime plan-invariant verifier (plan/verify.py)
    "planInvariantViolation",
    # encoded columnar execution (columnar/encoding.py, transfer.py)
    "encodedBatch", "encodingFallback",
    # host-transition & device-sync ledger (aux/transitions.py, schema
    # v4): one hostTransition per packed H2D/D2H transfer, one
    # deviceSync per non-transfer blocking sync
    "hostTransition", "deviceSync",
    # shuffle layer (shuffle/*.py, exec/exchange.py)
    "shuffleSend", "shuffleFetch", "fetchRetry", "fetchFailover",
    "shuffleBlockLoaded", "shuffleWorkerFetch", "shuffleBlocksInvalidated",
    "executorRegistered", "executorLost", "workerExpired", "mapRerun",
    "collectiveFallback",
    # SPMD partitioned execution (parallel/mesh.py, parallel/spmd.py,
    # plan/distribution.py, exec/adaptive.py)
    "meshTopology", "iciExchange", "exchangeElided", "aqeCoalesce",
    # chaos / resilience (aux/faults.py)
    "faultInjected", "breakerTrip",
    # runtime lock-order validator (aux/lockorder.py)
    "lockOrderViolation",
    # live resource sampler (aux/sampler.py)
    "resourceSample",
    # live engine console (aux/console.py): start/stop/dump lifecycle
    "consoleLifecycle",
    # concurrent query serving (serving/server.py, serving/caches.py):
    # admission lifecycle, the two cross-query caches, and the online
    # AutoTuner's applied conf deltas
    "servingAdmission", "planCache", "resultCache", "autotuneApplied",
    # calibrated cost-model cross-check (aux/tracing.py): predicted vs
    # measured wall time from the tools/history machine profile
    "costModel",
})


@dataclasses.dataclass
class Event:
    """One observability record.  ``ts`` is ``time.monotonic()`` — event
    ordering within a query is meaningful, wall-clock is not."""
    kind: str
    query_id: int
    span_id: int
    ts: float
    payload: Dict

    def to_json(self) -> str:
        return json.dumps({"event": self.kind, "query_id": self.query_id,
                           "span_id": self.span_id, "ts": self.ts,
                           "v": EVENT_SCHEMA_VERSION, **self.payload},
                          default=str)


def parse_event_line(line: str) -> Event:
    """Inverse of ``Event.to_json`` (the round-trip contract the event-log
    schema test pins): raises on lines missing the required envelope."""
    d = json.loads(line)
    kind = d.pop("event")
    query_id = d.pop("query_id")
    span_id = d.pop("span_id")
    ts = d.pop("ts")
    d.pop("v", None)
    return Event(kind, query_id, span_id, ts, d)


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

class EventSink:
    def emit(self, event: Event) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class _DropCell:
    """One ring's drop count, kept alive past the ring itself: at ring
    GC a finalizer retires the cell's value into the process total, so
    ``ring_dropped_total()`` stays monotonic without the hot emit path
    ever touching a process-global lock."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


_DROP_LOCK = threading.Lock()
_RETIRED_DROPS = 0
_LIVE_DROP_CELLS: set = set()
#: cells whose ring was collected, not yet folded into ``_RETIRED_DROPS``
_DEAD_DROP_CELLS: list = []


def _retire_drop_cell(cell: _DropCell) -> None:
    # the ring's finalizer takes no lock: a garbage collection can run it
    # on a thread that already holds ``_DROP_LOCK``; the cell stays in
    # ``_LIVE_DROP_CELLS``, and so in the total, until it is folded
    _DEAD_DROP_CELLS.append(cell)


def _fold_dead_cells() -> None:
    """Moves collected rings' counts into the retired total; under
    ``_DROP_LOCK``."""
    global _RETIRED_DROPS
    while _DEAD_DROP_CELLS:
        cell = _DEAD_DROP_CELLS.pop()
        _LIVE_DROP_CELLS.discard(cell)
        _RETIRED_DROPS += cell.n


def ring_dropped_total() -> int:
    """Process-lifetime count of events dropped by ring-buffer sinks —
    the truncation marker ``render_prometheus()`` and the offline
    profiler surface so a silently-trimmed buffer is never mistaken for
    'nothing happened'."""
    with _DROP_LOCK:
        _fold_dead_cells()
        return _RETIRED_DROPS + sum(c.n for c in _LIVE_DROP_CELLS)


class RingBufferSink(EventSink):
    """Bounded in-memory sink (tests / explain(analyze)); drops oldest
    beyond ``capacity`` and counts the drops — a truncated buffer must
    never read as complete.  Drops also tally into the process-wide
    ``ring_dropped_total()`` counter (via a per-ring cell: the emit path
    only touches this ring's lock)."""

    def __init__(self, capacity: int = 2048):
        self._buf = collections.deque(maxlen=max(1, int(capacity)))
        self._lock = threading.Lock()
        self._drop_cell = _DropCell()
        with _DROP_LOCK:
            _fold_dead_cells()
            _LIVE_DROP_CELLS.add(self._drop_cell)
        weakref.finalize(self, _retire_drop_cell, self._drop_cell)

    @property
    def dropped(self) -> int:
        return self._drop_cell.n

    def emit(self, event: Event) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._drop_cell.n += 1
            self._buf.append(event)

    def events(self) -> List[Event]:
        with self._lock:
            return list(self._buf)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


#: live event-log sinks, flushed at interpreter exit so short-lived
#: processes (bench runs, scripts) don't lose the sub-batch tail
_LIVE_EVENTLOG_SINKS: "weakref.WeakSet" = weakref.WeakSet()
_ATEXIT_ARMED = False


def _flush_eventlog_sinks() -> None:
    """atexit hook (also directly testable): flush every live sink's
    pending lines without closing it."""
    for sink in list(_LIVE_EVENTLOG_SINKS):
        try:
            sink.flush()
        except Exception:   # noqa: BLE001 - exit path must not raise
            pass


def _register_eventlog_sink(sink: "JsonlEventLogSink") -> None:
    global _ATEXIT_ARMED
    _LIVE_EVENTLOG_SINKS.add(sink)
    if not _ATEXIT_ARMED:
        _ATEXIT_ARMED = True
        atexit.register(_flush_eventlog_sinks)


class JsonlEventLogSink(EventSink):
    """Appends one JSON object per event to ``path`` (Spark event-log
    analog; multiple queries interleave lines, keyed by ``query_id``).

    Line-atomic under concurrency: pending lines batch in memory and hit
    the O_APPEND fd in ONE unbuffered write per batch — a second query's
    sink on the same path can interleave between batches but never split
    a line (a torn line would break the ``parse_event_line`` contract).
    A stdio buffer would instead flush at SIZE boundaries, tearing lines
    mid-JSON.

    Hardening (conf ``spark.rapids.sql.eventLog.*``):

    - a fresh (empty) file opens with an ``eventLogHeader`` line carrying
      the schema version, so the offline reader knows what it is parsing;
    - ``max_bytes`` > 0 rotates the file once it crosses the budget: the
      current file renames to ``path.N`` (N increasing, oldest smallest)
      and a fresh file (with header) takes its place — the reader walks
      the rotated set in order;
    - ``compress=True`` writes each batch as ONE complete gzip member
      (``gzip.compress`` of the batch) to the O_APPEND fd, so the
      one-write-per-batch atomicity survives compression and readers see
      a standard multi-member gzip stream (sniffed by magic, not
      extension);
    - pending lines flush via ``atexit`` so short-lived processes don't
      lose the tail.
    """

    #: events between writes; emitters (which may hold the query or
    #: catalog lock) only pay disk latency once per batch
    FLUSH_EVERY = 64

    def __init__(self, path: str, max_bytes: int = 0,
                 compress: bool = False,
                 flush_every: Optional[int] = None):
        self.path = path
        self.max_bytes = max(0, int(max_bytes or 0))
        self.compress = bool(compress)
        self._flush_every = max(1, int(flush_every or self.FLUSH_EVERY))
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._pending: List[str] = []
        self._f = None
        self._open_file()
        _register_eventlog_sink(self)

    # -- file lifecycle ------------------------------------------------------
    def _open_file(self) -> None:
        self._f = open(self.path, "ab", buffering=0)
        if os.fstat(self._f.fileno()).st_size == 0:
            header = Event("eventLogHeader", NO_QUERY, NO_SPAN,
                           time.monotonic(),
                           {"format": "spark-rapids-tpu-eventlog",
                            "compress": self.compress})
            self._write_raw(header.to_json() + "\n")

    def _write_raw(self, text: str) -> None:
        data = text.encode("utf-8")
        if self.compress:
            data = gzip.compress(data)
        self._f.write(data)

    def _rotate_locked(self) -> None:
        self._f.close()
        n = 1
        while os.path.exists(f"{self.path}.{n}"):
            n += 1
        os.replace(self.path, f"{self.path}.{n}")
        self._open_file()

    # -- sink API ------------------------------------------------------------
    def emit(self, event: Event) -> None:
        with self._lock:
            if self._f.closed:
                return
            self._pending.append(event.to_json() + "\n")
            if len(self._pending) >= self._flush_every:
                self._write_pending()

    def _write_pending(self) -> None:
        if self._pending:
            self._write_raw("".join(self._pending))
            self._pending = []
        if not self.max_bytes:
            return
        # several sinks may share this path (per-query sinks + the
        # sampler's): judge the budget by the REAL file size, not this
        # sink's private write count, and never rename a file another
        # sink already rotated us away from — migrate to the fresh file
        # instead
        try:
            st_fd = os.fstat(self._f.fileno())
            st_path = os.stat(self.path)
        except OSError:
            return      # mid-rotation window elsewhere; re-check next batch
        if st_path.st_ino != st_fd.st_ino:
            self._f.close()
            self._open_file()
            return
        if st_fd.st_size >= self.max_bytes:
            self._rotate_locked()

    def flush(self) -> None:
        """Pushes pending lines to disk without closing (atexit hook)."""
        with self._lock:
            if not self._f.closed:
                self._write_pending()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._write_pending()
                self._f.close()


# ---------------------------------------------------------------------------
# routing: active query (contextvar) + per-thread span stack + global sinks
# ---------------------------------------------------------------------------

_ACTIVE: "contextvars.ContextVar[Optional[object]]" = contextvars.ContextVar(
    "srt_active_query", default=None)


def active_query():
    """The QueryExecution the calling context runs under, or None.
    Task-pool threads see the right query because iter_partition_tasks
    copies the submitting thread's context (plan/base.py)."""
    return _ACTIVE.get()


def _activate(query):
    return _ACTIVE.set(query)


def _deactivate(token) -> None:
    _ACTIVE.reset(token)


class _SpanStack(threading.local):
    def __init__(self):
        self.stack: List[int] = []


_SPANS = _SpanStack()


def push_span(span_id: int) -> None:
    """Marks the calling thread as executing inside ``span_id`` — events
    emitted deeper in the call stack (a spill inside a kernel staging
    alloc) attribute to the operator that triggered them."""
    _SPANS.stack.append(span_id)


def pop_span() -> None:
    if _SPANS.stack:
        _SPANS.stack.pop()


def current_span_id() -> Optional[int]:
    st = _SPANS.stack
    return st[-1] if st else None


_GLOBAL_SINKS: List[EventSink] = []
_GLOBAL_LOCK = threading.Lock()


def add_global_sink(sink: EventSink) -> None:
    """Receives events emitted OUTSIDE any query context (heartbeat
    threads, shuffle worker processes, the resource sampler)."""
    with _GLOBAL_LOCK:
        _GLOBAL_SINKS.append(sink)


def remove_global_sink(sink: EventSink) -> None:
    with _GLOBAL_LOCK:
        if sink in _GLOBAL_SINKS:
            _GLOBAL_SINKS.remove(sink)


#: the console's process-wide event tail (aux/console.py /events): a
#: RingBufferSink mirror of BOTH routing paths — query-scoped events
#: (mirrored by QueryExecution.record_event) and global-scope events
#: (mirrored here).  None when the console is off: the emit hot path
#: pays one module-global read, nothing else.
_CONSOLE_TAP: Optional[RingBufferSink] = None


def set_console_tap(sink: Optional[RingBufferSink]) -> None:
    global _CONSOLE_TAP
    _CONSOLE_TAP = sink


def console_tap() -> Optional[RingBufferSink]:
    return _CONSOLE_TAP


def emit(kind: str, **payload) -> None:
    """The one hook every layer calls.  No active query, no global
    sink and no console tap = no allocation, no lock."""
    q = _ACTIVE.get()
    if q is not None:
        q.record_event(kind, payload)
        return
    tap = _CONSOLE_TAP
    if _GLOBAL_SINKS or tap is not None:
        ev = Event(kind, NO_QUERY, current_span_id() or NO_SPAN,
                   time.monotonic(), payload)
        with _GLOBAL_LOCK:
            sinks = list(_GLOBAL_SINKS)
        for s in sinks:
            s.emit(ev)
        if tap is not None:
            tap.emit(ev)


# ---------------------------------------------------------------------------
# Prometheus-style exposition of the process-wide registries
# ---------------------------------------------------------------------------

def escape_label_value(v: str) -> str:
    """Prometheus exposition-format label escaping: backslash, double
    quote and newline must be escaped inside label values."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def render_prometheus() -> str:
    """Text exposition of the runtime's gauges/counters (catalog tiers,
    task-metric accumulators, semaphore, operator ranges) in the
    Prometheus format a scraper or test can parse."""
    lines: List[str] = []

    def add(name: str, mtype: str, value, help_text: str) -> None:
        full = f"spark_rapids_tpu_{name}"
        lines.append(f"# HELP {full} {help_text}")
        lines.append(f"# TYPE {full} {mtype}")
        lines.append(f"{full} {value}")

    from spark_rapids_tpu.memory.device_manager import get_runtime
    rt = get_runtime()
    if rt is not None:
        st = rt.catalog.stats()
        add("device_pool_bytes", "gauge", st["device_bytes"],
            "Catalog-tracked device bytes")
        add("device_pool_limit_bytes", "gauge", st["device_limit"],
            "Device pool budget")
        add("device_pool_peak_bytes", "gauge", st["device_peak_bytes"],
            "High-watermark of catalog-tracked device bytes")
        add("device_spillable_bytes", "gauge", st["spillable_bytes"],
            "Device-tier bytes the spill framework may evict")
        add("host_spill_bytes", "gauge", st["host_bytes"],
            "Catalog-tracked host-tier bytes")
        add("disk_spill_bytes", "gauge", st["disk_bytes"],
            "Catalog-tracked disk-tier bytes")
        add("catalog_buffers", "gauge", st["buffers"],
            "Live buffers in the catalog")
        add("spill_total", "counter", st["spill_count"],
            "Buffers pushed down a storage tier")
        total, finished = rt.metrics.snapshot()
        add("tasks_finished_total", "counter", finished,
            "Tasks reported to the metrics registry")
        add("retry_total", "counter", total.retry_count,
            "RetryOOM attempts across tasks")
        add("split_retry_total", "counter", total.split_retry_count,
            "SplitAndRetryOOM splits across tasks")
        add("oom_total", "counter", total.oom_count,
            "Device pool exhaustions signalled to tasks")
        add("task_spill_bytes_total", "counter", total.spill_bytes,
            "Bytes spilled attributed to tasks")
        add("semaphore_wait_seconds_total", "counter",
            round(total.semaphore_wait_seconds, 6),
            "Seconds tasks blocked on device admission")
        add("alloc_wait_seconds_total", "counter",
            round(total.alloc_wait_seconds, 6),
            "Seconds tasks parked in BLOCKED_ON_ALLOC awaiting releases")
        add("semaphore_max_concurrent", "gauge",
            rt.semaphore.max_concurrent,
            "Device admission permits (concurrentGpuTasks)")
    from spark_rapids_tpu.memory.arbiter import get_arbiter
    ast = get_arbiter().stats()
    add("arbiter_blocked_threads", "gauge", ast["blocked_threads"],
        "Task threads currently in a blocked arbiter state")
    add("arbiter_blocked_on_alloc_total", "counter",
        ast["blocked_on_alloc_total"],
        "Allocation parks taken by the cooperative arbiter")
    add("deadlock_breaks_total", "counter", ast["deadlock_breaks"],
        "Forced victim wakes by the deadlock detector")
    add("forced_splits_total", "counter", ast["forced_splits"],
        "Deadlock breaks escalated to SplitAndRetryOOM")
    add("tasks_cancelled_total", "counter", ast["tasks_cancelled"],
        "Wedged tasks cancelled by the hung-query watchdog")
    add("watchdog_dumps_total", "counter", ast["watchdog_dumps"],
        "Hung-query watchdog thread-state dumps")
    add("serving_queries", "gauge", ast["serving_queries"],
        "Queries currently admitted to or queued in the serving layer")
    add("serving_admission_queued", "gauge", ast["serving_queued"],
        "Submissions currently blocked on serving admission "
        "(BLOCKED_ON_ADMISSION)")
    add("events_ring_dropped_total", "counter", ring_dropped_total(),
        "Events dropped by bounded ring-buffer sinks (truncation marker)")
    from spark_rapids_tpu.aux import lockorder as _lo
    add("lock_order_violations_total", "counter", _lo.violations_total(),
        "Lock acquisitions that went backward against the canonical "
        "order (spark.rapids.debug.lockOrder validator; 0 when disarmed)")
    from spark_rapids_tpu.plan import verify as _pv
    add("plan_invariant_violations_total", "counter",
        _pv.violations_total(),
        "Structural plan-contract violations found by the runtime plan "
        "verifier (spark.rapids.debug.planCheck; 0 when disarmed)")
    from spark_rapids_tpu.exec import stage_compiler as _sc
    scs = _sc.stats()
    add("stage_programs", "gauge", scs["programs"],
        "Live compiled stage programs in the executable cache")
    add("stage_cache_hits_total", "counter", scs["hits"],
        "Executable-cache hits (program reused without rebuild)")
    add("stage_cache_misses_total", "counter", scs["misses"],
        "Executable-cache misses (program built)")
    add("stage_cache_evictions_total", "counter", scs["evictions"],
        "Programs dropped by the executable-cache LRU bound")
    add("stage_traces_total", "counter", scs["traces"],
        "JAX traces of stage programs (retrace marker: should stop "
        "growing once a workload's shapes are warm)")
    add("stage_compiles_total", "counter", scs["compiles"],
        "Stage programs compiled (first dispatches)")
    add("stage_async_compiles_total", "counter", scs["async_compiles"],
        "Stage programs compiled on the background pool")
    add("stage_compile_seconds_total", "counter",
        round(scs["compile_s"], 6),
        "Seconds spent tracing+compiling stage programs")
    from spark_rapids_tpu.aux import transitions as _tr
    trt = _tr.totals()
    add("h2d_transitions_total", "counter", trt["h2d_count"],
        "Packed host->device batch uploads through the transition gateway")
    add("h2d_bytes_total", "counter", trt["h2d_bytes"],
        "Bytes uploaded host->device")
    add("h2d_seconds_total", "counter", trt["h2d_seconds"],
        "Seconds in device_put dispatch for H2D uploads")
    add("d2h_transitions_total", "counter", trt["d2h_count"],
        "Packed device->host batch downloads through the transition "
        "gateway")
    add("d2h_bytes_total", "counter", trt["d2h_bytes"],
        "Bytes downloaded device->host")
    add("d2h_seconds_total", "counter", trt["d2h_seconds"],
        "Seconds blocked fetching D2H downloads")
    add("device_syncs_total", "counter", trt["sync_count"],
        "Non-transfer blocking device syncs (count forces, overflow "
        "checks) through the transition gateway")
    add("device_sync_seconds_total", "counter", trt["sync_seconds"],
        "Seconds blocked in non-transfer device syncs")
    from spark_rapids_tpu.serving import server as _srv
    hists = _srv.latency_histograms()
    if hists:
        full = "spark_rapids_tpu_serving_latency_seconds"
        lines.append(f"# HELP {full} Serving submission latency by stage "
                     "(queue wait, admission, cache lookup, plan, "
                     "compile, execute, collect, e2e)")
        lines.append(f"# TYPE {full} histogram")
        for stage in sorted(hists):
            h = hists[stage]
            lbl = escape_label_value(stage)
            for le, n in h["buckets"]:
                le_s = "+Inf" if le == float("inf") else repr(le)
                lines.append(f'{full}_bucket{{stage="{lbl}",le="{le_s}"}} '
                             f'{n}')
            lines.append(f'{full}_sum{{stage="{lbl}"}} '
                         f'{round(h["sum"], 6)}')
            lines.append(f'{full}_count{{stage="{lbl}"}} {h["count"]}')
    return "\n".join(lines) + "\n"
