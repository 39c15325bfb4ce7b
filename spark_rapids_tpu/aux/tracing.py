"""Query-scoped observability: the span tree + metric/event funnel.

Reference: the plugin's per-exec ``GpuMetric`` map + ``GpuTaskMetrics``
accumulators + the Spark SQL UI's per-query execution graph.  A
``QueryExecution`` plays the SQLExecution role: it assigns a query id,
mirrors the physical plan as a span tree (one span per exec node, child
spans for partitions = tasks), and funnels every existing signal into
one place —

- ``OpMetric`` counters from ``instrument_plan`` (rows/batches/opTime),
- ``TaskMetrics`` deltas from the runtime's ``MetricsRegistry``
  (spill bytes, retry/split-retry/OOM counts, semaphore wait),
- events emitted by the memory / shuffle layers (``aux.events.emit``),
  attributed to the operator span whose pull triggered them.

``DataFrame.explain(analyze=True)`` and bench attribution render from
here; the JSONL event log (``spark.rapids.sql.eventLog.path``) receives
queryStart / spanMetrics / queryEnd plus every layer event.

:func:`span` is THE span primitive of the query path.  One call writes the
same span to two places: the active query's tree (a ``phase`` span: name,
start, end, the span that caused it) and the JAX profiler's trace (a
``TraceAnnotation`` named ``srt.<name>`` carrying ``query_id`` and
``span_id``, on the device events' clock).  With no profiler trace running
the annotation is an inert TraceMe, so nothing has to be switched on.  The
span vocabulary is a contract (docs/observability.md, PERF.md):
``plan.parse``, ``plan.analyze``, ``plan.rewrite``, ``exec.run``,
``exec.replay``, ``xfer.h2d``, ``xfer.d2h``, ``xfer.sync``,
``compile.build``, ``result.rows``, ``device.permit`` (a wait for the
device semaphore), ``task.run`` (one partition task), ``exchange.write``
(a map batch's partition ids, split and store), ``exchange.read`` (a
reduce partition handed on), ``broadcast.build`` (a broadcast join's build
side pulled and concatenated, then keyed), a served query's
``serve.queue``, ``serve.admit`` and ``serve.lookup``; annotation only:
``dispatch`` and ``exec.<node name>`` (one per batch pull).

Attribution rule: what a query's summary counts (``dispatches``, the
transition ledger, the task metrics, ``compile_s``) is added to the
active query where the work happens (``EV.active_query()``, which the
task pools carry into their threads), never taken as a difference of
process totals.  So over any set of queries, alone or overlapping, the
summaries' counts add up to the process's delta, and a text counts the
same in a crowd as alone.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import heapq
import itertools
import threading
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.aux import events as EV

_query_ids = itertools.count(1)
_span_ids = itertools.count(1)

_LAST_LOCK = threading.Lock()
_LAST_SUMMARY: Optional[dict] = None

#: process-wide registry of in-flight QueryExecutions (registered on
#: __enter__, removed at finish) + a bounded tail of finished summaries.
#: The console's /queries endpoint reads both; the registry is a plain
#: dict under its own leaf lock so a scrape never touches engine locks.
_LIVE_LOCK = threading.Lock()
_LIVE: Dict[int, "QueryExecution"] = {}
#: 1024 summaries: a served benchmark window reads its queries back from here
_RECENT: collections.deque = collections.deque(maxlen=1024)


def live_queries() -> List["QueryExecution"]:
    """The QueryExecutions currently in flight in this process."""
    with _LIVE_LOCK:
        return list(_LIVE.values())


def recent_summaries() -> List[dict]:
    """Bounded tail (newest last) of finished-query summary dicts."""
    with _LIVE_LOCK:
        return list(_RECENT)


def last_query_summary() -> Optional[dict]:
    """Summary dict of the most recently finished query in this
    process."""
    with _LAST_LOCK:
        return _LAST_SUMMARY


#: the most gap intervals a summary lists (``device_gaps.intervals``)
MAX_GAP_INTERVALS = 512

_CLOCK_LOCK = threading.Lock()
_CLOCK_ANCHOR: Optional[dict] = None


def clock_anchor() -> dict:
    """The span tree's clock (``time.monotonic``) and the profiler's host
    clock read together, once a process: ``{"monotonic_s", "wall_ns"}``.
    A profiler trace stamps its host and device events with
    ``time.time_ns()``, less its ``profile_start_time`` (a stat of the
    xplane's ``Task Environment`` plane), so an instant ``t`` of a summary
    lies at ``wall_ns + 1e9 * (t - monotonic_s) - profile_start_time``
    nanoseconds of the trace."""
    global _CLOCK_ANCHOR
    with _CLOCK_LOCK:
        if _CLOCK_ANCHOR is None:
            m0 = time.monotonic_ns()
            wall = time.time_ns()
            m1 = time.monotonic_ns()
            _CLOCK_ANCHOR = {"monotonic_s": (m0 + m1) / 2e9, "wall_ns": wall}
        return _CLOCK_ANCHOR


def _coverage(intervals):
    """``measure(a, b)``: how much of ``[a, b]`` the sorted, disjoint
    ``intervals`` cover (a bisection, not a scan)."""
    starts = [s for s, _e in intervals]
    ends = [e for _s, e in intervals]
    before = list(itertools.accumulate(
        (e - s for s, e in intervals), initial=0.0))

    def upto(x):
        i = bisect.bisect_right(starts, x)
        return 0.0 if i == 0 else \
            before[i - 1] + min(x, ends[i - 1]) - starts[i - 1]

    return lambda a, b: upto(b) - upto(a) if b > a else 0.0


def _nondefault_conf(conf) -> dict:
    """Registered conf values that differ from their defaults, JSON-safe.
    Rides the queryStart event so the offline AutoTuner recommends FROM
    the session's actual settings (an absent key = registry default)."""
    from spark_rapids_tpu import config as C
    out = {}
    for key, entry in C.registry().items():
        try:
            v = conf.get(key)
        except Exception:   # noqa: BLE001 - snapshot must never fail a query
            continue
        if v != entry.default:
            out[key] = v if isinstance(v, (bool, int, float)) else str(v)
    return out


class Span:
    """One node of the query's span tree.  ``kind`` is ``query`` (root),
    ``exec`` (one physical plan node), ``partition`` (one task of an
    exec node) or ``phase`` (one layer boundary of the query path, opened
    by :func:`span`; ``metrics`` holds its attributes)."""

    __slots__ = ("span_id", "parent_id", "name", "desc", "kind", "device",
                 "children", "start", "end", "metrics", "rows", "batches",
                 "padded_rows", "pidx")

    def __init__(self, name: str, parent_id: Optional[int] = None,
                 desc: str = "", kind: str = "exec", device: bool = False,
                 pidx: Optional[int] = None):
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.name = name
        self.desc = desc or name
        self.kind = kind
        self.device = device
        self.children: List[Span] = []
        self.start = time.monotonic()
        self.end: Optional[float] = None
        self.metrics: Dict = {}
        self.rows = 0
        self.batches = 0
        #: sum of the buckets of the batches pulled (rows incl. padding)
        self.padded_rows = 0
        self.pidx = pidx

    @property
    def duration_s(self) -> float:
        return (self.end if self.end is not None else time.monotonic()) \
            - self.start


#: the task-metric part of a summary: its tasks' ``TaskMetrics``, summed
#: (``max_device_bytes``: the largest)
_TASKS_ZERO = {"tasks": 0, "retry_count": 0, "split_retry_count": 0,
               "oom_count": 0, "spill_count": 0, "spill_bytes": 0,
               "semaphore_wait_s": 0.0, "alloc_wait_s": 0.0,
               "max_device_bytes": 0}

#: a query's transition ledger: its own crossings of the gateway
_LEDGER_ZERO = {"h2d_count": 0, "h2d_bytes": 0, "h2d_s": 0.0,
                "d2h_count": 0, "d2h_bytes": 0, "d2h_s": 0.0,
                "sync_count": 0, "sync_s": 0.0}

#: event kinds folded into per-node attribution at finish
_ATTR_ZERO = {"spill_count": 0, "spill_bytes": 0, "retry_count": 0,
              "split_retry_count": 0, "oom_count": 0,
              "blocked_count": 0, "blocked_wait_s": 0.0,
              "deadlock_breaks": 0}


class QueryExecution:
    """Context manager scoping one query (one DataFrame action).

    Entering activates this query for the context (and, through the
    task pool's contextvar copies, for every task thread of the query);
    ``attach_plan`` builds the exec-span tree from the physical plan the
    overrides produced; exiting harvests metrics, emits
    spanMetrics/queryEnd, and publishes the summary."""

    def __init__(self, description: str = "",
                 sinks: Optional[List[EV.EventSink]] = None,
                 ring_size: int = 2048):
        self.query_id = next(_query_ids)
        self.description = description
        self.root = Span("query", kind="query", desc=description or "query")
        self.ring = EV.RingBufferSink(ring_size)
        self._sinks = list(sinks or [])
        self._lock = threading.Lock()
        #: id(node.metrics) -> exec span.  The metrics dict is the stable
        #: identity: plan rewrites shallow-copy nodes but SHARE the
        #: metrics dict, so the instrumentation wrapper (bound to the
        #: dict) and the attached plan's copies resolve to the same span.
        self._node_spans: Dict[int, Span] = {}
        self._span_index: Dict[int, Span] = {self.root.span_id: self.root}
        self._plan = None
        #: the exec span of the attached plan's root
        self._plan_span: Optional[Span] = None
        self._token = None
        #: counts noted where the work happens (:func:`add_count`)
        self.counters: Dict[str, int] = {"speculation_replays": 0,
                                         "pair_rows_padded": 0,
                                         "expand_rows_padded": 0,
                                         "probe_gather_rounds": 0,
                                         "sized_joins": 0,
                                         "direct_joins": 0,
                                         "join_cols_pruned": 0,
                                         "sized_stages": 0,
                                         "broadcast_builds": 0,
                                         "exchanges": 0,
                                         "exchange_rows": 0,
                                         "exchange_rows_padded": 0,
                                         "exchange_pieces": 0,
                                         "exchange_host_staged_bytes": 0}
        #: what this query's own threads did, added by the layer that did
        #: it: steady dispatches (``exec/stage_compiler.py``), the
        #: gateway's ledger (``aux/transitions.py``), its finished tasks'
        #: metrics (``memory/metrics.py``) and its compiles
        self._dispatches_by_kind: Dict[str, int] = {}
        self._dispatch_s = 0.0
        self._compile_s = 0.0
        self._ledger = dict(_LEDGER_ZERO)
        self._tasks = dict(_TASKS_ZERO)
        #: the gateway's gap ledger, as far as it is this query's: the
        #: device gaps its device work closed, ``(start, end, kind, site,
        #: late)``, and its ``unplaced`` and ``eager_device_calls`` counts
        self._gaps: List[tuple] = []
        self._gap_counts = {"unplaced": 0, "eager_device_calls": 0}
        #: facts of the query that the layer above knows (:func:`note`):
        #: a served query's ``resolved`` and ``plan_cache``
        self.notes: Dict = {}
        self.summary_dict: Optional[dict] = None
        self.finished = False
        #: cached predict_plan_costs rows for the attached plan (fixed
        #: weights keep the live progress fraction monotone) + a
        #: high-water mark so reported progress never regresses across
        #: console scrapes even when a new partition wave opens
        self._live_cost: Optional[List[Dict]] = None
        self._live_cost_key: Optional[int] = None
        self._progress_hwm = 0.0
        #: non-default conf values captured at from_conf (v2 event-log
        #: schema: rides the queryStart payload so the offline AutoTuner
        #: knows what it is tuning FROM)
        self.conf_snapshot: Dict = {}

    @staticmethod
    def from_conf(conf=None, description: str = "") -> "QueryExecution":
        from spark_rapids_tpu import config as C
        sinks: List[EV.EventSink] = []
        ring = 2048
        if conf is not None:
            path = conf.get(C.EVENT_LOG_PATH.key, "")
            if path:
                sinks.append(EV.JsonlEventLogSink(
                    path,
                    max_bytes=conf.get(C.EVENT_LOG_MAX_BYTES.key, 0),
                    compress=conf.get(C.EVENT_LOG_COMPRESS.key, False)))
            ring = conf.get(C.EVENT_LOG_RING_SIZE.key, 2048)
        qe = QueryExecution(description, sinks, ring)
        if conf is not None:
            qe.conf_snapshot = _nondefault_conf(conf)
        return qe

    # -- lifecycle -----------------------------------------------------------
    def __enter__(self) -> "QueryExecution":
        self._token = EV._activate(self)
        start_payload = {"description": self.description}
        if self.conf_snapshot:
            start_payload["conf"] = dict(self.conf_snapshot)
        self.record_event("queryStart", start_payload)
        with _LIVE_LOCK:
            _LIVE[self.query_id] = self
        return self

    def __exit__(self, exc_type, exc, tb):
        try:
            self.finish(error=exc)
        finally:
            EV._deactivate(self._token)
        return False

    # -- span tree -----------------------------------------------------------
    def _current_span_locked(self) -> Span:
        """The span the calling thread runs under, else the root."""
        sid = EV.current_span_id()
        return self._span_index.get(sid, self.root) if sid is not None \
            else self.root

    def attach_plan(self, plan) -> None:
        """Mirrors the executed physical plan as exec spans under the
        span the caller runs in (``exec.run``; the root outside one).
        Re-attaching the same plan moves its spans there; another plan (a
        speculation replay re-applies the overrides) replaces them, so
        the tree holds the plan that actually runs: already-recorded
        events keep their span ids and fall back to the root for
        attribution."""
        with self._lock:
            parent = self._current_span_locked()
            old = self._plan_span
            if old is not None:
                holder = self._span_index.get(old.parent_id, self.root)
                if old in holder.children:
                    holder.children.remove(old)
                if plan is self._plan:
                    old.parent_id = parent.span_id
                    parent.children.append(old)
                    return

                def forget(sp: Span) -> None:
                    self._span_index.pop(sp.span_id, None)
                    for c in sp.children:
                        forget(c)

                forget(old)
            self._plan = plan
            self._node_spans.clear()

            def build(node, parent: Span) -> Span:
                sp = Span(node.name, parent.span_id, desc=node.node_desc(),
                          device=getattr(node, "is_device", False))
                parent.children.append(sp)
                self._span_index[sp.span_id] = sp
                self._node_spans[id(getattr(node, "metrics", None))] = sp
                for c in node.children:
                    build(c, sp)
                return sp

            self._plan_span = build(plan, parent)

    def open_span(self, name: str, attrs: dict) -> Span:
        """A ``phase`` child of the span the calling thread runs under
        (:func:`span` pushes and closes it)."""
        with self._lock:
            parent = self._current_span_locked()
            sp = Span(name, parent.span_id, kind="phase")
            sp.metrics = attrs
            parent.children.append(sp)
            self._span_index[sp.span_id] = sp
            return sp

    def adopt(self, planned) -> None:
        """Takes closed ``(name, start, end)`` intervals (:func:`timed_span`:
        ``plan.parse``/``plan.analyze`` of the text this query runs, a
        served query's ``serve.*``) as children of the root, with their
        own times; one may lie inside another.  What they cover of the
        time before the query began counts in ``duration_s``."""
        with self._lock:
            for name, start, end in planned:
                sp = Span(name, self.root.span_id, kind="phase")
                sp.start, sp.end = start, end
                self.root.children.append(sp)
                self._span_index[sp.span_id] = sp

    def add_count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def note_dispatch(self, kind: str, seconds: float) -> None:
        """One steady call of a built stage program, by this query."""
        with self._lock:
            self._dispatch_s += seconds
            self._dispatches_by_kind[kind] = \
                self._dispatches_by_kind.get(kind, 0) + 1

    def note_compiled(self, seconds: float) -> None:
        with self._lock:
            self._compile_s += seconds

    def note_transition(self, door: str, seconds: float,
                        nbytes: int = 0) -> None:
        """One crossing of the gateway by this query: ``door`` is
        ``h2d``, ``d2h`` or ``sync``."""
        with self._lock:
            led = self._ledger
            led[door + "_count"] += 1
            led[door + "_s"] += seconds
            if nbytes:
                led[door + "_bytes"] += nbytes

    def note_device_work(self, gap, end: float, unplaced: bool,
                         eager: bool) -> None:
        """Device work of this query closed ``gap`` (the gateway's open
        gap, or None) at ``end``, found the device drained with none open
        (``unplaced``), or was an eager call (``transitions.device_work``)."""
        with self._lock:
            if gap is not None:
                start, site, fetcher, late = gap
                kind = "fetch" if fetcher == self.query_id else "query"
                self._gaps.append((start, end, kind, site, late))
            self._gap_counts["unplaced"] += unplaced
            self._gap_counts["eager_device_calls"] += eager

    def note_task(self, m) -> None:
        """A finished task's ``TaskMetrics`` (``memory/metrics.py``)."""
        with self._lock:
            t = self._tasks
            t["tasks"] += 1
            for key in ("retry_count", "split_retry_count", "oom_count",
                        "spill_count", "spill_bytes"):
                t[key] += getattr(m, key)
            t["semaphore_wait_s"] += m.semaphore_wait_seconds
            t["alloc_wait_s"] += m.alloc_wait_seconds
            t["max_device_bytes"] = max(t["max_device_bytes"],
                                        m.max_device_bytes)

    def start_partition(self, node_key: int, pidx: int) -> Span:
        """Child span for one partition (task) of an exec node; called by
        the instrumentation wrapper at generator start."""
        with self._lock:
            parent = self._node_spans.get(node_key, self.root)
            sp = Span(f"partition-{pidx}", parent.span_id,
                      kind="partition", pidx=pidx)
            parent.children.append(sp)
            self._span_index[sp.span_id] = sp
            return sp

    def end_partition(self, span: Span) -> None:
        span.end = time.monotonic()

    def events(self) -> List[EV.Event]:
        return self.ring.events()

    # -- live console view ---------------------------------------------------
    def span_names(self) -> Dict[int, str]:
        """span_id -> operator name for every span of this query (the
        console joins BufferCatalog attribution tags through this)."""
        with self._lock:
            return {sid: sp.name for sid, sp in self._span_index.items()}

    def _cost_predictions_locked(self) -> Optional[List[Dict]]:
        """Pre-order per-node prediction rows for the attached plan,
        cached per plan identity (attach_plan builds exec spans in the
        same pre-order, so row i describes exec span i).  With a
        configured machine profile the rows carry ``predicted_s`` from
        the calibrated fit (the cost model's first live consumer);
        without one they still carry ``estimate_rows`` so per-node
        progress fractions work profile-free.  Caller holds _lock."""
        plan = self._plan
        if plan is None:
            return None
        if self._live_cost_key == id(plan):
            return self._live_cost
        rows: Optional[List[Dict]] = None
        try:
            from spark_rapids_tpu import config as C
            from spark_rapids_tpu.plan import cost as PC
            path = self.conf_snapshot.get(
                C.HISTORY_MACHINE_PROFILE_PATH.key)
            enabled = self.conf_snapshot.get(
                C.HISTORY_COST_MODEL_ENABLED.key,
                C.HISTORY_COST_MODEL_ENABLED.default)
            profile = (PC.load_machine_profile(str(path))
                       if path and enabled else None)
            if profile is not None:
                rows = PC.predict_plan_costs(plan, profile, live=True)
            else:
                rows = []

                def walk(node) -> None:
                    rows.append({"node": type(node).__name__,
                                 "rows": PC.estimate_rows(node),
                                 "predicted_s": None})
                    for c in node.children:
                        walk(c)

                walk(plan)
        except Exception:   # noqa: BLE001 - console view, never fails a query
            rows = None
        self._live_cost = rows
        self._live_cost_key = id(plan)
        return rows

    def live_snapshot(self) -> dict:
        """Point-in-time JSON view of this query for the console
        /queries endpoint: the exec-span tree with rows/batches so far
        (summed from the live partition child spans — OpMetric values
        only harvest into exec spans at finish), plus a progress
        fraction and an ETA joined against the machine-profile cost
        predictions.  Reads only this query's own lock."""
        now = time.monotonic()
        with self._lock:
            execs = self._exec_spans()
            preds = self._cost_predictions_locked()
            if preds is not None and len(preds) != len(execs):
                preds = None    # replay attached a different-shape plan
            finished = self.finished
            summary = self.summary_dict
            nodes = []
            weighted_total = 0.0
            weighted_done = 0.0
            profiled = False
            for i, sp in enumerate(execs):
                parts = [c for c in sp.children if c.kind == "partition"]
                live_rows = sum(c.rows for c in parts)
                live_batches = sum(c.batches for c in parts)
                if finished and sp.metrics:
                    live_rows = int(sp.metrics.get("numOutputRows",
                                                   live_rows) or 0)
                    live_batches = int(sp.metrics.get("numOutputBatches",
                                                      live_batches) or 0)
                pred = preds[i] if preds is not None else None
                pred_rows = int(pred["rows"]) if pred else None
                pred_s = pred.get("predicted_s") if pred else None
                if pred_s is not None:
                    profiled = True
                done = len(parts) > 0 and all(c.end is not None
                                              for c in parts)
                if finished or done:
                    frac = 1.0
                elif pred_rows:
                    frac = min(1.0, live_rows / max(1, pred_rows))
                else:
                    frac = 0.0
                weight = max(float(pred_s), 1e-9) \
                    if pred_s is not None else 1.0
                weighted_total += weight
                weighted_done += weight * frac
                nodes.append({
                    "span_id": sp.span_id, "parent_id": sp.parent_id,
                    "node": sp.name, "desc": sp.desc[:120],
                    "device": sp.device,
                    "rows": live_rows, "batches": live_batches,
                    "partitions": len(parts),
                    "partitions_done": sum(1 for c in parts
                                           if c.end is not None),
                    "predicted_rows": pred_rows,
                    "predicted_s": pred_s,
                    "frac": round(frac, 6),
                })
            if finished:
                progress = 1.0
            elif weighted_total > 0:
                progress = weighted_done / weighted_total
            else:
                progress = 0.0
            # high-water mark: a fresh partition wave lowers a node's
            # raw fraction; the reported number must stay monotone
            progress = max(progress, self._progress_hwm)
            self._progress_hwm = progress
            elapsed = ((self.root.end if self.root.end is not None
                        else now) - self.root.start)
            eta_s: Optional[float] = None
            eta_source: Optional[str] = None
            if finished:
                eta_s, eta_source = 0.0, "finished"
            elif profiled and weighted_done > 0:
                # calibrate the profile's absolute scale to this run:
                # remaining predicted seconds x (elapsed / completed
                # predicted seconds)
                eta_s = ((weighted_total - weighted_done)
                         * (elapsed / weighted_done))
                eta_source = "machine_profile"
            elif progress > 0:
                eta_s = elapsed * (1.0 - progress) / progress
                eta_source = "elapsed_extrapolation"
            snap = {
                "query_id": self.query_id,
                "description": self.description,
                "status": "finished" if finished else "running",
                "elapsed_s": round(elapsed, 6),
                "progress": round(progress, 6),
                "eta_s": (None if eta_s is None else round(eta_s, 6)),
                "eta_source": eta_source,
                "nodes": nodes,
            }
            if finished and summary is not None:
                snap["status"] = summary.get("status", "finished")
                snap["duration_s"] = summary.get("duration_s")
            return snap

    # -- event funnel --------------------------------------------------------
    def record_event(self, kind: str, payload: dict,
                     span_id: Optional[int] = None) -> None:
        with self._lock:
            sid = span_id if span_id is not None else EV.current_span_id()
            if sid is None or sid not in self._span_index:
                sid = self.root.span_id
            # ts assigned AND delivered under the lock: sink (file) order
            # is timestamp order, which the event-log schema test pins
            ev = EV.Event(kind, self.query_id, sid, time.monotonic(),
                          dict(payload))
            self.ring.emit(ev)
            for s in self._sinks:
                s.emit(ev)
            tap = EV.console_tap()
            if tap is not None:
                tap.emit(ev)

    def _attribute_events(self) -> Dict[int, dict]:
        """Folds layer events onto their exec span (partition spans roll
        up to their parent node) for per-node spill/retry columns."""
        per: Dict[int, dict] = {}
        for ev in self.ring.events():
            # span ids orphaned by a replay's attach_plan rebuild fall
            # back to the root so pressure events still count
            sp = self._span_index.get(ev.span_id) or self.root
            while sp.kind in ("partition", "phase"):
                # a transfer inside an operator's pull belongs to the node
                sp = self._span_index.get(sp.parent_id, self.root)
            if sp.kind == "query" and ev.kind not in ("spill", "retryOOM",
                                                      "splitRetry", "oom",
                                                      "threadBlocked",
                                                      "deadlockBreak"):
                continue
            d = per.setdefault(sp.span_id, dict(_ATTR_ZERO))
            if ev.kind == "spill":
                d["spill_count"] += 1
                d["spill_bytes"] += int(ev.payload.get("bytes", 0))
            elif ev.kind == "retryOOM":
                d["retry_count"] += 1
            elif ev.kind == "splitRetry":
                d["split_retry_count"] += 1
            elif ev.kind == "oom":
                d["oom_count"] += 1
            elif ev.kind == "threadBlocked":
                d["blocked_count"] += 1
                d["blocked_wait_s"] = round(
                    d["blocked_wait_s"]
                    + float(ev.payload.get("wait_s", 0.0) or 0.0), 6)
            elif ev.kind == "deadlockBreak":
                d["deadlock_breaks"] += 1
        return per

    # -- finish / summary ----------------------------------------------------
    def finish(self, error=None) -> dict:
        if self.finished:
            return self.summary_dict
        self.finished = True
        now = time.monotonic()
        # harvest final OpMetric values into the exec spans
        plan = self._plan
        if plan is not None:
            with self._lock:
                node_spans = dict(self._node_spans)
            for node in plan.collect_nodes():
                ms = getattr(node, "metrics", None) or {}
                sp = node_spans.get(id(ms))
                if sp is None:
                    continue
                sp.end = now
                for m in ms.values():
                    m.resolve()
                sp.metrics = {m.name: (round(m.value, 6)
                                       if isinstance(m.value, float)
                                       else m.value)
                              for m in ms.values()}
        attr = self._attribute_events()
        # recovery ledger: what resilience cost THIS query (chaos/fault
        # recovery transitions emitted by the shuffle/task layers; the
        # kind->key vocabulary lives in aux/faults.py)
        from spark_rapids_tpu.aux.faults import RECOVERY_KINDS
        recovery: Dict[str, int] = {}
        for ev in self.ring.events():
            key = RECOVERY_KINDS.get(ev.kind)
            if key is not None:
                recovery[key] = recovery.get(key, 0) + 1
        self.root.end = now
        # span depth map: the offline reader (tools/reader.py) rebuilds
        # the tree from parent_id/depth — the in-memory children links
        # don't survive the JSONL round trip
        depths: Dict[int, int] = {}

        def _depth_walk(sp: Span, d: int) -> None:
            depths[sp.span_id] = d
            for c in sp.children:
                _depth_walk(c, d + 1)

        _depth_walk(self.root, 0)
        nodes = []
        for sp in self._exec_spans():
            row = {"span_id": sp.span_id, "parent_id": sp.parent_id,
                   "depth": depths.get(sp.span_id, 1), "node": sp.name,
                   "desc": sp.desc[:120], "device": sp.device,
                   "start_s": round(sp.start, 6),
                   "end_s": round(sp.end if sp.end is not None else now, 6),
                   **sp.metrics}
            parts = [{"pidx": c.pidx, "start_s": round(c.start, 6),
                      "end_s": round(c.end if c.end is not None else now, 6),
                      "rows": c.rows, "batches": c.batches,
                      "padded_rows": c.padded_rows}
                     for c in sp.children if c.kind == "partition"]
            if parts:
                row["partitions"] = parts
            extra = attr.get(sp.span_id)
            if extra:
                row.update({k: v for k, v in extra.items() if v})
            nodes.append(row)
            self.record_event("spanMetrics", row, span_id=sp.span_id)
        for sp in self._phase_spans():
            self.record_event("spanMetrics", {
                "span_id": sp.span_id, "parent_id": sp.parent_id,
                "depth": depths.get(sp.span_id, 1), "node": sp.name,
                "kind": "phase", "start_s": round(sp.start, 6),
                "end_s": round(sp.end if sp.end is not None else now, 6),
                **sp.metrics}, span_id=sp.span_id)
        phases, early_s = self._phase_self_times(now)
        with self._lock:
            counters = dict(self.counters)
            tasks = {k: round(v, 6) if isinstance(v, float) else v
                     for k, v in self._tasks.items()}
            by_kind = dict(self._dispatches_by_kind)
            dispatch_s, compile_s = self._dispatch_s, self._compile_s
            ledger = {k: round(v, 6) if isinstance(v, float) else v
                      for k, v in self._ledger.items()}
            notes = dict(self.notes)
        summary = {
            "query_id": self.query_id,
            "description": self.description,
            "status": "error" if error is not None else "ok",
            # what the client waited: the action, and what the spans it
            # adopted cover of the time before it began (the planning of
            # its text in ``sql()``, a served query's queue and admission)
            "duration_s": round(self.root.duration_s + early_s, 6),
            "events": len(self.ring) + self.ring.dropped,
            "events_dropped": self.ring.dropped,
            **notes,
            **tasks,
            **counters,
            "phases": phases,
            "nodes": nodes,
            "dispatches": sum(by_kind.values()),
            "dispatch_s": round(dispatch_s, 6),
            "dispatches_by_kind": by_kind,
            "compile_s": round(compile_s, 6),
        }
        if recovery:
            summary["recovery"] = recovery
        # host-transition ledger: this query's own crossings of the
        # gateway (aux/transitions.py)
        from spark_rapids_tpu.aux import transitions as TR
        if TR.enabled():
            summary["transitions"] = ledger
            summary["device_gaps"] = self._device_gaps(now)
        summary["clock"] = clock_anchor()
        # calibrated cost-model cross-check (report-only; docs/history.md):
        # predicted wall time from the tools/history machine profile vs
        # this query's measured duration, emitted before sinks close so
        # the residual lands in the event log for `tools audit`
        cost = self._cost_crosscheck(plan, summary["duration_s"])
        if cost is not None:
            summary["cost"] = cost
            self.record_event("costModel", cost)
        self.summary_dict = summary
        self.record_event("queryEnd",
                          {k: v for k, v in summary.items()
                           if k != "nodes"})
        for s in self._sinks:
            s.close()
        global _LAST_SUMMARY
        with _LAST_LOCK:
            _LAST_SUMMARY = summary
        with _LIVE_LOCK:
            _LIVE.pop(self.query_id, None)
            _RECENT.append(summary)
        return summary

    def _cost_crosscheck(self, plan, measured_s: float):
        """Predicted-vs-measured residual against the configured machine
        profile, or None when no profile is set/loadable.  Defaults are
        absent from ``conf_snapshot`` (non-default-only), so a missing
        path key simply means the cost model is off."""
        if plan is None:
            return None
        from spark_rapids_tpu import config as C
        path = self.conf_snapshot.get(C.HISTORY_MACHINE_PROFILE_PATH.key)
        enabled = self.conf_snapshot.get(
            C.HISTORY_COST_MODEL_ENABLED.key,
            C.HISTORY_COST_MODEL_ENABLED.default)
        if not path or not enabled:
            return None
        try:
            from spark_rapids_tpu.plan.cost import (load_machine_profile,
                                                    predict_plan_costs)
            profile = load_machine_profile(str(path))
            if profile is None:
                return None
            rows = predict_plan_costs(plan, profile)
            predicted = sum(r["predicted_s"] for r in rows
                            if r["predicted_s"] is not None)
            covered = sum(1 for r in rows
                          if r["predicted_s"] is not None)
            residual = ((measured_s - predicted) / measured_s
                        if measured_s > 0 else 0.0)
            return {"profile_version": profile.version,
                    "residual_bound": profile.residual_bound,
                    "predicted_s": round(predicted, 6),
                    "measured_s": round(measured_s, 6),
                    "residual": round(residual, 6),
                    "nodes": len(rows), "covered": covered}
        except Exception:   # noqa: BLE001 - report-only, never fails a query
            return None

    def _spans_of(self, kind: str) -> List[Span]:
        out: List[Span] = []

        def walk(sp: Span) -> None:
            if sp.kind == kind:
                out.append(sp)
            for c in sp.children:
                walk(c)

        walk(self.root)
        return out

    def _exec_spans(self) -> List[Span]:
        return self._spans_of("exec")

    def _phase_spans(self) -> List[Span]:
        return self._spans_of("phase")

    def _phase_self_times(self, now: float, within=None):
        """``({span name: self seconds}, early seconds)`` over the phase
        spans: a span's duration less the part that the phase spans
        inside it cover (the choosing-metrics rule).  Every instant goes
        to the innermost phase span open then (the latest opened where
        threads overlap, or where one adopted span lies inside another);
        an instant of the root's interval with none open goes to
        ``(unattributed)``, one before the root began to nothing.  The
        early seconds are what the adopted spans cover of the time before
        the root began, so the entries add up to the root's duration plus
        those: ``duration_s``.

        ``within`` (sorted, disjoint ``(start, end)`` intervals: the
        query's device gaps) counts only the instants inside them, and
        leaves none out: one with no span open goes to ``(between
        queries)`` before the first phase span began (the root, where none
        did) and to ``(unattributed)`` from then on."""
        lo, hi = self.root.start, (self.root.end if self.root.end
                                   is not None else now)
        spans = []      # (depth among phase spans, start, name)
        edges = []      # (time, 0 = close | 1 = open, index)
        out: Dict[str, float] = {"(unattributed)": 0.0}

        def walk(sp: Span, depth: int) -> None:
            for c in sp.children:
                d = depth
                if c.kind == "phase":
                    d = depth + 1
                    out.setdefault(c.name, 0.0)     # a span of no length
                    end = min(c.end if c.end is not None else now, hi)
                    if end > c.start:
                        edges.append((c.start, 1, len(spans)))
                        edges.append((end, 0, len(spans)))
                        spans.append((d, c.start, c.name))
                walk(c, d)

        with self._lock:
            walk(self.root, 0)
        edges.sort()
        first = min([lo] + [start for _d, start, _n in spans])
        if within is None:
            at = lo

            def measure(a, b):
                return b - a

            def none_open(a, b):
                if b > lo:
                    out["(unattributed)"] += b - max(a, lo)
        else:
            at = min([first] + [start for start, _e in within[:1]])
            measure = _coverage(within)
            out["(between queries)"] = 0.0

            def none_open(a, b):
                out["(between queries)"] += measure(a, min(b, first))
                out["(unattributed)"] += measure(max(a, first), b)
        early = 0.0
        open_heap: List = []    # (-depth, -start, index): innermost first
        closed = set()
        for t, opens, i in edges:
            while open_heap and open_heap[0][2] in closed:
                heapq.heappop(open_heap)
            if open_heap:
                name = spans[open_heap[0][2]][2]
                out[name] = out.get(name, 0.0) + measure(at, t)
                early += max(0.0, min(t, lo) - at)
            else:
                none_open(at, t)
            at = t
            if opens:
                heapq.heappush(open_heap, (-spans[i][0], -spans[i][1], i))
            else:
                closed.add(i)
        none_open(at, hi)
        return {k: round(v, 6) for k, v in out.items()}, early

    def _device_gaps(self, now: float) -> dict:
        """The summary's ``device_gaps``: the gaps this query's device work
        closed, by kind, by the span its threads were in (the phases'
        rule, over the gaps' instants) and by the fetch that drained the
        device, with the intervals themselves (the first
        ``MAX_GAP_INTERVALS``)."""
        with self._lock:
            gaps = sorted(self._gaps)
            counts = dict(self._gap_counts)
        by_kind = {"fetch": 0.0, "query": 0.0}
        by_site: Dict[str, float] = {}
        for start, end, kind, site, _late in gaps:
            by_kind[kind] += end - start
            by_site[site] = by_site.get(site, 0.0) + end - start
        by_span = self._phase_self_times(
            now, within=[(g[0], g[1]) for g in gaps])[0] if gaps else {}
        return {"fetch_s": round(by_kind["fetch"], 6),
                "query_s": round(by_kind["query"], 6),
                "count": len(gaps),
                "late": sum(1 for g in gaps if g[4]),
                **counts,
                "by_span": {k: v for k, v in by_span.items() if v > 0},
                "by_site": {k: round(v, 6) for k, v in by_site.items()},
                "intervals": [[round(g[0], 6), round(g[1], 6), g[2], g[3]]
                              for g in gaps[:MAX_GAP_INTERVALS]],
                "intervals_dropped": max(0, len(gaps) - MAX_GAP_INTERVALS)}

    # -- rendering -----------------------------------------------------------
    def render_tree(self, show_partitions: bool = False) -> str:
        """The EXPLAIN ANALYZE body: the plan tree annotated with
        rows/batches/opTime (and spill/retry where attributed), plus the
        query-level summary footer."""
        attr = self._attribute_events()
        lines = [f"== Analyzed Plan: query {self.query_id} "
                 f"{self.description!r} ({self.root.duration_s:.3f}s) =="]

        _SHORT = {"numOutputRows": "rows", "numOutputBatches": "batches",
                  "opTime": "opTime", "streamTime": "streamTime",
                  # pipelining boundaries (exec/pipeline.py): measured
                  # overlap per boundary — how long each side of the spool
                  # waited on the other, and the deepest the queue ran
                  "producerStallTime": "pStall",
                  "consumerStallTime": "cStall",
                  "peakQueueDepth": "qDepth"}

        def fmt(sp: Span) -> str:
            bits = []
            for key, short in _SHORT.items():
                if key in sp.metrics:
                    v = sp.metrics[key]
                    bits.append(f"{short}={v}{'s' if 'Time' in key else ''}")
            extra = attr.get(sp.span_id) or {}
            for k, v in extra.items():
                if v:
                    bits.append(f"{k}={v}")
            return f" [{' '.join(bits)}]" if bits else ""

        def walk(sp: Span, indent: int) -> None:
            if sp.kind == "partition":
                if not show_partitions:
                    return
                lines.append("  " * indent
                             + f"{sp.name} rows={sp.rows} "
                             f"batches={sp.batches} "
                             f"time={sp.duration_s:.4f}s")
                return
            mark = "*" if sp.device else " "
            lines.append("  " * indent + mark + sp.desc + fmt(sp))
            for c in sp.children:
                walk(c, indent + 1)

        if self._plan_span is not None:
            walk(self._plan_span, 0)
        summary = self.summary_dict or {}
        phases = summary.get("phases")
        if phases:
            lines.append("== Phases (self time) ==")
            lines.append(" ".join(f"{k}={v}s" for k, v in phases.items()))
            lines.append(" ".join(
                f"{k}={summary[k]}" for k in
                ("dispatches", "dispatch_s", "speculation_replays",
                 "pair_rows_padded", "expand_rows_padded",
                 "probe_gather_rounds", "sized_joins", "direct_joins",
                 "join_cols_pruned", "sized_stages", "broadcast_builds",
                 "exchanges", "exchange_rows",
                 "exchange_rows_padded", "exchange_pieces",
                 "exchange_host_staged_bytes")
                if k in summary))
        lines.append("== Query Summary ==")
        lines.append(" ".join(
            f"{k}={summary[k]}" for k in
            ("tasks", "retry_count", "split_retry_count", "oom_count",
             "spill_count", "spill_bytes", "semaphore_wait_s",
             "alloc_wait_s", "max_device_bytes") if k in summary))
        rec = summary.get("recovery")
        if rec:
            lines.append("== Recovery ==")
            lines.append(" ".join(f"{k}={v}" for k, v in sorted(
                rec.items())))
        tr = summary.get("transitions")
        if tr:
            lines.append("== Transitions ==")
            lines.append(
                f"h2d={tr.get('h2d_count', 0)} "
                f"({tr.get('h2d_bytes', 0)}B {tr.get('h2d_s', 0.0)}s) "
                f"d2h={tr.get('d2h_count', 0)} "
                f"({tr.get('d2h_bytes', 0)}B {tr.get('d2h_s', 0.0)}s) "
                f"syncs={tr.get('sync_count', 0)} "
                f"({tr.get('sync_s', 0.0)}s)")
        gaps = summary.get("device_gaps")
        if gaps:
            lines.append("== Device gaps ==")
            lines.append(" ".join(f"{k}={gaps[k]}" for k in (
                "fetch_s", "query_s", "count", "late", "unplaced",
                "eager_device_calls")))
        return "\n".join(lines)


def annotation(name: str, q, span_id, **attrs):
    """The profiler annotation ``srt.<name>`` with the query's id and a
    span's: an inert TraceMe unless a profiler trace is running."""
    # imported where it is used: the package does not import jax before
    # the first device use
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(
        "srt." + name,
        query_id=q.query_id if q is not None else EV.NO_QUERY,
        span_id=span_id if span_id is not None else EV.NO_SPAN, **attrs)


@contextlib.contextmanager
def _running_under(name: str, q, sp: Optional[Span], attrs: dict):
    """Annotates ``srt.<name>`` and, where there is a span, runs the
    thread under it."""
    with annotation(name, q, sp.span_id if sp is not None else None,
                    **attrs):
        if sp is None:
            yield
            return
        EV.push_span(sp.span_id)
        try:
            yield
        finally:
            EV.pop_span()


@contextlib.contextmanager
def span(name: str, **attrs):
    """THE span primitive: one layer boundary of the query path, written
    to two places.  With an active query it opens a ``phase`` child of
    the span the thread runs under (the root outside one) and yields it;
    it always enters the profiler annotation ``srt.<name>`` with the
    query's id and the span's, which is inert unless a profiler trace is
    running.  Outside any query (or after the query has finished) only
    the annotation is written, and ``None`` is yielded."""
    q = EV.active_query()
    sp = q.open_span(name, attrs) if q is not None and not q.finished \
        else None
    try:
        with _running_under(name, q, sp, attrs):
            yield sp
    finally:
        if sp is not None:
            sp.end = time.monotonic()


_DRAINED = object()


def span_pulls(name: str, it, **attrs):
    """:func:`span` over the life of an iterator that other code pulls
    from: ONE span (opened at the first pull, closed when ``it`` is
    drained or the generator is closed), under which every pull of ``it``
    runs, each inside the annotation ``srt.<name>``; closing the generator
    closes ``it``.  Between pulls the thread is the consumer's and runs
    under the consumer's span, so a ``with span(...)`` around a ``yield``
    (whose push and pop the consumer could interleave with its own) is
    never needed."""
    q = EV.active_query()
    sp = q.open_span(name, attrs) if q is not None and not q.finished \
        else None
    try:
        while True:
            with _running_under(name, q, sp, attrs):
                item = next(it, _DRAINED)
            if item is _DRAINED:
                return
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:   # closed early: the source stops now
            close()
        if sp is not None:
            sp.end = time.monotonic()


@contextlib.contextmanager
def timed_span(name: str, into: list, start: Optional[float] = None,
               **attrs):
    """:func:`span`, for work that is done before its query opens (a
    text's planning in ``TpuSession.sql``, a served query's queue,
    admission and lookup): the closed interval ``(name, start, end)`` is
    appended to ``into``, for the query to adopt (``query_scope``'s
    ``planned``) and for the caller's own sums, so both read one clock.
    ``start`` backdates the interval to a wait that no thread ran inside
    (``serve.queue``: the annotation then marks its end)."""
    t0 = time.monotonic() if start is None else start
    sp = None
    try:
        with span(name, **attrs) as sp:
            if sp is not None:      # inside a query: the tree's own clock
                sp.start = t0
            yield sp
    finally:
        into.append((name, t0, sp.end if sp is not None
                     else time.monotonic()))


def partition_pull(q: Optional[QueryExecution], pspan: Optional[Span],
                   node_name: str):
    """One pull of an operator's partition iterator: the thread runs
    under the partition span for its length, and the profiler's trace
    gets ``srt.exec.<node name>``."""
    return _running_under("exec." + node_name, q, pspan, {})


@contextlib.contextmanager
def run_span(plan):
    """``exec.run``: the root's ``collect_host`` / ``execute_all``.  The
    plan's exec spans (and through them the partition spans) hang under
    it."""
    with span("exec.run") as sp:
        if sp is not None:
            EV.active_query().attach_plan(plan)
        yield sp


def add_count(name: str, n: int = 1) -> None:
    """Adds to a per-query counter of the active query's summary
    (``speculation_replays``, ``pair_rows_padded``,
    ``expand_rows_padded``, ``probe_gather_rounds``, ``sized_joins``,
    ``direct_joins``, ``join_cols_pruned``, ``sized_stages``,
    ``broadcast_builds``, ``exchanges`` and the exchange's
    ``exchange_*``), where the work happens."""
    q = EV.active_query()
    if q is not None:
        q.add_count(name, n)


def note(**fields) -> None:
    """Sets fields of the active query's summary that only the layer
    above the query knows: a served query's ``resolved`` and
    ``plan_cache`` (``serving/``)."""
    q = EV.active_query()
    if q is not None:
        with q._lock:
            q.notes.update(fields)


@contextlib.contextmanager
def query_scope(conf=None, description: str = "", planned=()):
    """Action-level wrapper: opens a QueryExecution unless one is already
    active (nested actions — cache materialization, explain(analyze) —
    join the outer query) or tracing is disabled by conf.  ``planned``
    holds the closed intervals of what was done for this action before it
    (:func:`timed_span`); the query that opens adopts them."""
    active = EV.active_query()
    if active is not None:
        if planned:
            active.adopt(planned)
        yield active
        return
    if conf is not None:
        from spark_rapids_tpu import config as C
        if not conf.get(C.TRACING_ENABLED.key, True):
            yield None
            return
    qe = QueryExecution.from_conf(conf, description)
    with qe:
        if planned:
            qe.adopt(planned)
        yield qe
