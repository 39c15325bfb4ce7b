"""QueryServer: concurrent multi-query serving over one engine runtime.

One server owns:

- a **worker pool** (``spark.rapids.serving.maxConcurrentQueries``
  threads) draining a submission queue;
- an **admission controller**: before executing, every query reserves
  device-pool bytes (``queryMemoryReservation``, Sparkle-style static
  partitioning of the shared pool) and waits — with timeout + doubling
  backoff — while the reservations don't fit.  Waits are surfaced
  through the PR 7 arbiter registry (``BLOCKED_ON_ADMISSION`` in
  ``stats()``/``dump()``) and emit ``servingAdmission`` events.  A
  starved pool BLOCKS submissions (then sheds them with
  :class:`AdmissionTimeout`); it never OOMs the engine;
- the two **cross-query caches** (serving/caches.py): an exact repeat
  of a query skips planning and compilation entirely (asserted by test
  via the stage compiler's trace counters), and a deterministic repeat
  skips execution too (result cache), both invalidated when any input
  file changes;
- the **online AutoTuner loop** (``serving.autotune.enabled``): after
  each query the PR 5 rule set evaluates the query's live event ring +
  the resource sampler's ``resourceSample`` feed; accepted deltas (an
  explicit allowlist: pipeline depth, concurrentGpuTasks, batch size)
  apply to the server conf — and the live semaphore — so they take
  effect for the NEXT admitted query, each emitting ``autotuneApplied``.

Per-query conf travels WITH the plan (the round-5 knobs ride exec
instances; the conf digest keys the plan cache), which is what makes
admitting N queries with evolving confs sound.
"""

from __future__ import annotations

import hashlib
import queue
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Union

from spark_rapids_tpu import config as C
from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.serving.caches import PlanCache, ResultCache
from spark_rapids_tpu.serving.signature import (conf_digest,
                                                plan_fingerprints,
                                                plan_signature)

#: conf keys the online tuner may change between queries; everything
#: else a rule recommends is reported (stats) but never auto-applied
ONLINE_TUNABLE_KEYS = frozenset({
    "spark.rapids.pipeline.depth",
    "spark.rapids.sql.concurrentGpuTasks",
    "spark.rapids.sql.batchSizeBytes",
})


class AdmissionTimeout(TimeoutError):
    """The submission waited past ``serving.queueTimeoutMs`` — the
    bounded queue sheds load instead of stacking it."""


#: serving latency histogram bounds (seconds): log-spaced from the
#: millisecond serving floor (ROADMAP item 2) up past the queue timeout
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, float("inf"))

#: submission stage keys, decomposition order (queue wait -> admission
#: -> cache lookup -> plan -> compile -> execute -> collect)
STAGE_KEYS = ("queue_wait_s", "admit_wait_s", "lookup_s", "plan_s",
              "compile_s", "execute_s", "collect_s")

#: the stages that are sums of a submission's spans of one name
#: (``Submission.spans``): the histogram, the ``servingAdmission`` event
#: and the query's ``phases`` read the same intervals.  ``serve.lookup``
#: is text to DataFrame, signature, fingerprints and the result-cache
#: probe, its re-check after admission, and the plan-cache lookup
STAGE_SPANS = {"queue_wait_s": "serve.queue", "admit_wait_s": "serve.admit",
               "lookup_s": "serve.lookup"}


class LatencyHistogram:
    """One fixed-bucket latency histogram (Prometheus semantics: the
    exposition renders CUMULATIVE ``le`` buckets + ``_sum``/``_count``)."""

    def __init__(self, bounds=LATENCY_BUCKETS):
        self.bounds = tuple(bounds)
        self._counts = [0] * len(self.bounds)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        v = max(0.0, float(seconds))
        with self._lock:
            for i, b in enumerate(self.bounds):
                if v <= b:
                    self._counts[i] += 1
                    break
            self._sum += v
            self._count += 1

    def snapshot(self) -> Dict:
        """Cumulative (le, count) pairs ending at +Inf, plus sum/count."""
        with self._lock:
            counts = list(self._counts)
            total, n = self._sum, self._count
        cum = 0
        buckets = []
        for b, c in zip(self.bounds, counts):
            cum += c
            buckets.append((b, cum))
        return {"buckets": buckets, "sum": total, "count": n}


#: process-wide stage -> histogram registry, rendered by
#: aux.events.render_prometheus (lazy import there; one registry per
#: process regardless of how many QueryServers run)
_HISTOGRAMS: Dict[str, LatencyHistogram] = {}
_HIST_LOCK = threading.Lock()


def observe_latency(stage: str, seconds: float) -> None:
    with _HIST_LOCK:
        h = _HISTOGRAMS.get(stage)
        if h is None:
            h = _HISTOGRAMS[stage] = LatencyHistogram()
    h.observe(seconds)


def latency_histograms() -> Dict[str, Dict]:
    """stage -> histogram snapshot for render_prometheus()."""
    with _HIST_LOCK:
        items = list(_HISTOGRAMS.items())
    return {stage: h.snapshot() for stage, h in items}


#: process-wide registry of running QueryServers (weak: a dropped,
#: never-stopped server must not leak here).  The console's /server
#: endpoint discovers live servers through it.
_SERVERS: "weakref.WeakSet" = weakref.WeakSet()
_SERVERS_LOCK = threading.Lock()


def live_servers() -> List["QueryServer"]:
    with _SERVERS_LOCK:
        return [s for s in _SERVERS if not s._stopped]


class AdmissionController:
    """Per-query memory reservations against the shared device pool.

    Admission succeeds when (a) a worker slot exists (callers are the
    bounded worker pool, so this is structural) and (b) the sum of
    admitted reservations + this query's fits the pool limit.  Waits
    ride a condition variable with doubling-backoff re-checks and are
    registered in the arbiter's serving view."""

    def __init__(self, max_concurrent: int, reserve_bytes: int,
                 timeout_ms: int, backoff_ms: int):
        self.max_concurrent = int(max_concurrent)
        self._reserve_bytes = int(reserve_bytes)
        self.timeout_ms = int(timeout_ms)
        self.backoff_ms = int(backoff_ms)
        self._cond = threading.Condition()
        self._admitted: Dict[int, int] = {}        # query id -> reserved
        self.stats = {"admitted": 0, "queued": 0, "timeouts": 0,
                      "queue_wait_s": 0.0}

    def _pool_limit(self) -> Optional[int]:
        from spark_rapids_tpu.memory.device_manager import get_runtime
        rt = get_runtime()
        return rt.catalog.device_limit if rt is not None else None

    def reservation_for(self, limit: Optional[int]) -> int:
        if self._reserve_bytes > 0:
            return self._reserve_bytes
        if limit is None:
            return 0
        return max(1, limit // max(1, self.max_concurrent))

    def _fits(self, reserve: int, limit: Optional[int]) -> bool:
        if limit is None or reserve <= 0:
            return True
        if len(self._admitted) >= self.max_concurrent:
            return False
        used = sum(self._admitted.values())
        # the FIRST query always admits even when its reservation alone
        # exceeds the pool (the arbiter + spill tier absorb a genuinely
        # oversized working set; admission only orders peers)
        return not self._admitted or used + reserve <= limit

    def admit(self, query_id: int, timeout_ms: Optional[int] = None,
              deadline: Optional[float] = None) -> int:
        """Blocks until admitted; returns the reserved byte count.
        Raises :class:`AdmissionTimeout` past the queue timeout.
        ``deadline`` (monotonic) wins over ``timeout_ms`` — the server
        passes ``submitted + queueTimeoutMs`` so time spent waiting for
        a WORKER counts against the same budget as the admission wait.
        The deadline bounds WAITING only, deliberately: a submission
        that can run the moment a worker picks it up runs even if its
        deadline lapsed in the worker queue — shedding runnable work a
        client is still blocked on would waste the whole wait."""
        from spark_rapids_tpu.memory.arbiter import TaskState, get_arbiter
        arb = get_arbiter()
        limit = self._pool_limit()
        reserve = self.reservation_for(limit)
        if deadline is None:
            deadline = time.monotonic() + \
                (timeout_ms if timeout_ms is not None else self.timeout_ms) \
                / 1000.0
        backoff = max(0.001, self.backoff_ms / 1000.0)
        waited = None
        timed_out = None
        n_admitted = 0
        with self._cond:
            while not self._fits(reserve, limit):
                now = time.monotonic()
                if waited is None:
                    waited = now
                    self.stats["queued"] += 1
                    # the arbiter registration + event emit pay foreign
                    # locks and possibly sink file I/O: drop the
                    # condition around them so queueing one waiter never
                    # taxes every OTHER waiter's wake/notify, then loop
                    # back to re-check _fits (state may have moved)
                    self._cond.release()
                    try:
                        arb.note_serving(query_id,
                                         TaskState.BLOCKED_ON_ADMISSION,
                                         reserve)
                        EV.emit("servingAdmission", op="queued",
                                serve_id=query_id, reserve_bytes=reserve)
                    finally:
                        self._cond.acquire()
                    continue
                if now >= deadline:
                    # collect the facts under the lock, raise outside it
                    self.stats["timeouts"] += 1
                    timed_out = now
                    n_admitted = len(self._admitted)
                    break
                self._cond.wait(min(backoff, deadline - now))
                backoff = min(backoff * 2, 32 * self.backoff_ms / 1000.0)
                limit = self._pool_limit()
            if timed_out is None:
                self._admitted[query_id] = reserve
                wait_s = 0.0 if waited is None \
                    else time.monotonic() - waited
                self.stats["admitted"] += 1
                self.stats["queue_wait_s"] += wait_s
        if timed_out is not None:
            arb.drop_serving(query_id)
            EV.emit("servingAdmission", op="timeout", serve_id=query_id,
                    waited_s=round(timed_out - waited, 4))
            raise AdmissionTimeout(
                f"query {query_id} not admitted within "
                f"{self.timeout_ms}ms (pool limit {limit}, "
                f"reservation {reserve}B, "
                f"{n_admitted} admitted)")
        arb.note_serving(query_id, TaskState.RUNNING, reserve)
        EV.emit("servingAdmission", op="admitted", serve_id=query_id,
                reserve_bytes=reserve, queue_wait_s=round(wait_s, 4))
        return reserve

    def release(self, query_id: int) -> None:
        from spark_rapids_tpu.memory.arbiter import get_arbiter
        with self._cond:
            self._admitted.pop(query_id, None)
            self._cond.notify_all()
        get_arbiter().drop_serving(query_id)
        EV.emit("servingAdmission", op="released", serve_id=query_id)


class Submission:
    """Handle for one submitted query."""

    _UNSET = object()

    def __init__(self, serve_id: int, tag: str):
        self.serve_id = serve_id
        self.tag = tag
        self.submitted = time.monotonic()
        self._done = threading.Event()
        self._batch = Submission._UNSET
        self.error: Optional[BaseException] = None
        #: how this query resolved: "result_cache" | "plan_cache" |
        #: "planned"; plus timing (``latency_s`` = submit-to-finish,
        #: queue wait included — the number a serving SLO is made of)
        self.info: Dict = {}
        #: closed ``(name, start, end)`` intervals of the ``serve.*`` spans
        #: and of the text's planning (``aux.tracing.timed_span``), in the
        #: order they closed; the query that runs adopts those that closed
        #: before it opened
        self.spans: List[tuple] = []

    def _finish(self, batch=None, error=None) -> None:
        stages = self.info.get("stages")
        if stages is not None:
            for key, name in STAGE_SPANS.items():
                stages[key] = round(sum(end - start for n, start, end
                                        in self.spans if n == name), 6)
        self.info["latency_s"] = round(time.monotonic() - self.submitted, 6)
        self._batch = batch
        self.error = error
        self._done.set()

    def batch(self, timeout: Optional[float] = None):
        """The result HostColumnarBatch (blocks)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"query {self.serve_id} still running")
        if self.error is not None:
            raise self.error
        return self._batch

    def result(self, timeout: Optional[float] = None) -> List[dict]:
        """Rows as list-of-dicts (DataFrame.collect semantics)."""
        from spark_rapids_tpu.session import rows_from_host_batch
        return rows_from_host_batch(self.batch(timeout))


class QueryServer:
    """See module docstring.  ``queries`` are SQL text (against the
    session's temp views), DataFrames, or callables
    ``session -> DataFrame`` (re-invoked per execution)."""

    _ids = __import__("itertools").count(1)

    def __init__(self, session=None, conf=None):
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.session import TpuSession
        if session is None:
            if isinstance(conf, dict):
                conf = TpuConf(conf)
            session = TpuSession(conf)
        self.session = session
        self._conf = session.conf
        self._conf_lock = threading.Lock()
        cf = self._conf
        self.admission = AdmissionController(
            int(cf.get(C.SERVING_MAX_CONCURRENT.key)),
            C.parse_bytes(cf.get(C.SERVING_MEMORY_RESERVATION.key)),
            int(cf.get(C.SERVING_QUEUE_TIMEOUT_MS.key)),
            int(cf.get(C.SERVING_QUEUE_BACKOFF_MS.key)))
        self.plan_cache = PlanCache(
            int(cf.get(C.SERVING_PLAN_CACHE_MAX.key)),
            max_bytes=C.parse_bytes(
                cf.get(C.SERVING_PLAN_CACHE_MAX_BYTES.key)))
        self.result_cache = ResultCache(
            C.parse_bytes(cf.get(C.SERVING_RESULT_CACHE_MAX_BYTES.key)),
            spill=cf.get(C.SERVING_RESULT_CACHE_SPILL.key))
        self.autotune_enabled = cf.get(C.SERVING_AUTOTUNE_ENABLED.key)
        #: applied online deltas: [(key, old, new, reason, query_id)]
        self.autotune_applied: List[tuple] = []
        self._stopped = False
        #: orders submit() against stop() (an accepted submission is
        #: queued BEFORE the shutdown sentinels, so workers always
        #: process it and result() can never block forever) and guards
        #: the ring-sink registration toggle
        self._submit_lock = threading.Lock()
        #: out-of-query event capture (resourceSample feed for the
        #: tuner) — registered as a global sink only WHILE the online
        #: loop is on: every process-wide emit pays each installed sink,
        #: and a ring nobody reads is pure tax
        self._global_ring = EV.RingBufferSink(1024)
        self._ring_registered = False
        self._sync_ring_sink()
        #: (conf snapshot, digest) single-slot memo — see _conf_digest
        self._cdig = None
        self._queue: "queue.Queue" = queue.Queue()
        self._workers: List[threading.Thread] = []
        for i in range(self.admission.max_concurrent):
            t = threading.Thread(target=self._worker_loop,
                                 name=f"tpu-serve-{i}", daemon=True)
            t.start()
            self._workers.append(t)
        with _SERVERS_LOCK:
            _SERVERS.add(self)

    # -- conf ----------------------------------------------------------------
    @property
    def conf(self):
        with self._conf_lock:
            return self._conf

    def set_conf(self, key: str, value) -> "QueryServer":
        """Applies to queries admitted AFTER this call (the running ones
        keep the conf snapshot taken at their admission).  The serving
        layer's own knobs apply to the LIVE structures too — cache
        budgets resize (shrinking evicts immediately), queue timing
        updates — except ``maxConcurrentQueries``, which sizes the
        worker pool at construction."""
        with self._conf_lock:
            self._conf = self._conf.set(key, value)
            cf = self._conf
        if key.startswith("spark.rapids.serving."):
            self._apply_serving_conf(cf)
        return self

    def _conf_digest(self, conf) -> str:
        """conf_digest memoized on the snapshot's identity: TpuConf is
        immutable, the server conf only changes via set_conf, and
        re-canonicalizing the whole registry per submission would tax
        exactly the exact-hit path the caches exist to make cheap."""
        cached = self._cdig
        if cached is not None and cached[0] is conf:
            return cached[1]
        d = conf_digest(conf)
        self._cdig = (conf, d)
        return d

    def _apply_serving_conf(self, cf) -> None:
        self.result_cache.resize(
            C.parse_bytes(cf.get(C.SERVING_RESULT_CACHE_MAX_BYTES.key)))
        self.result_cache.spill_enabled = bool(
            cf.get(C.SERVING_RESULT_CACHE_SPILL.key))
        # plan-cache shrink trims lazily on the next insert (lookups
        # honor 0-disables immediately)
        self.plan_cache.max_plans = int(
            cf.get(C.SERVING_PLAN_CACHE_MAX.key))
        self.plan_cache.max_bytes = C.parse_bytes(
            cf.get(C.SERVING_PLAN_CACHE_MAX_BYTES.key))
        self.admission.timeout_ms = int(
            cf.get(C.SERVING_QUEUE_TIMEOUT_MS.key))
        self.admission.backoff_ms = int(
            cf.get(C.SERVING_QUEUE_BACKOFF_MS.key))
        self.autotune_enabled = cf.get(C.SERVING_AUTOTUNE_ENABLED.key)
        self._sync_ring_sink()

    def _sync_ring_sink(self) -> None:
        # under _submit_lock: concurrent set_conf calls must not
        # double-register, and set_conf racing (or following) stop()
        # must not resurrect the sink on a dead server
        with self._submit_lock:
            want = self.autotune_enabled and not self._stopped
            if want and not self._ring_registered:
                EV.add_global_sink(self._global_ring)
                self._ring_registered = True
            elif not want and self._ring_registered:
                EV.remove_global_sink(self._global_ring)
                self._ring_registered = False

    # -- submission ----------------------------------------------------------
    def submit(self, query: Union[str, object, Callable],
               tag: str = "") -> Submission:
        with self._submit_lock:
            if self._stopped:
                raise RuntimeError("QueryServer is stopped")
            sub = Submission(next(QueryServer._ids), tag or "query")
            self._queue.put((sub, query))
        return sub

    def execute(self, query, tag: str = "",
                timeout: Optional[float] = None) -> List[dict]:
        """Submit + wait: rows of one query."""
        return self.submit(query, tag).result(timeout)

    def stop(self) -> None:
        with self._submit_lock:
            self._stopped = True
            for _ in self._workers:
                self._queue.put(None)
        for t in self._workers:
            t.join(timeout=10.0)
        still_busy = [t for t in self._workers if t.is_alive()]
        self._workers = []
        # belt and suspenders: fail anything still queued (a worker that
        # died without draining) instead of leaving result() hanging
        drained_sentinels = 0
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is None:
                drained_sentinels += 1
            else:
                item[0]._finish(
                    error=RuntimeError("QueryServer stopped"))
        # a worker still running a long query past the join timeout will
        # come back to queue.get(): give each one its sentinel back or
        # it parks (and pins the server) forever
        for _ in range(min(drained_sentinels, len(still_busy))):
            self._queue.put(None)
        self._sync_ring_sink()      # _stopped -> always deregisters
        self.result_cache.clear()
        self.plan_cache.clear()
        with _SERVERS_LOCK:
            _SERVERS.discard(self)

    def stats(self) -> Dict:
        pc = dict(self.plan_cache.stats)
        pc["bytes"] = self.plan_cache.total_bytes
        pc["max_bytes"] = self.plan_cache.max_bytes
        pc["leased"] = self.plan_cache.leased_count()
        return {
            "admission": dict(self.admission.stats),
            "plan_cache": pc,
            "result_cache": dict(self.result_cache.stats),
            "autotune_applied": len(self.autotune_applied),
        }

    def live_stats(self) -> Dict:
        """Point-in-time serving state for the console /server endpoint
        (the cumulative ``stats()`` counters tell rates, not depth)."""
        with self.admission._cond:
            admitted_now = len(self.admission._admitted)
            reserved = sum(self.admission._admitted.values())
        return {
            "queue_depth": self._queue.qsize(),
            "admitted_now": admitted_now,
            "reserved_bytes": reserved,
            "max_concurrent": self.admission.max_concurrent,
            "stopped": self._stopped,
        }

    # -- worker --------------------------------------------------------------
    def _worker_loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            sub, query = item
            try:
                self._serve(sub, query)
            except BaseException as e:  # noqa: BLE001 - handed to caller
                sub._finish(error=e)

    def _build_df(self, query):
        if isinstance(query, str):
            return self.session.sql(query)
        if callable(query) and not hasattr(query, "_plan"):
            return query(self.session)
        return query

    def _serve(self, sub: Submission, query) -> None:
        from spark_rapids_tpu.aux.tracing import timed_span
        sub.info["stages"] = {k: 0.0 for k in STAGE_KEYS}
        with timed_span("serve.queue", sub.spans, start=sub.submitted):
            pass        # nothing ran in it: the annotation marks the pickup
        # result-cache probe BEFORE admission: a cached result needs no
        # device memory reservation, so a hit must not queue behind (or
        # steal a slot from) queries that actually execute
        try:
            probe = self._probe_result_cache(sub, query)
        except BaseException as e:  # noqa: BLE001 - handed to caller
            sub._finish(error=e)
            self._observe_stages(sub)
            return
        if probe.get("cached") is not None:
            sub.info["resolved"] = "result_cache"
            sub._finish(batch=probe["cached"])
            self._observe_stages(sub)
            return
        with timed_span("serve.admit", sub.spans):
            reserved = self.admission.admit(
                sub.serve_id,
                deadline=sub.submitted + self.admission.timeout_ms / 1000.0)
        try:
            # conf snapshot AT ADMISSION: online deltas accepted while
            # this query was queued apply to it; deltas accepted during
            # its run apply only to later admissions
            conf = self.conf
            sub.info["reserved_bytes"] = reserved
            batch = self._execute(sub, query, conf, probe=probe)
            sub._finish(batch=batch)
        except BaseException as e:  # noqa: BLE001 - handed to caller
            sub._finish(error=e)
        finally:
            self.admission.release(sub.serve_id)
            self._observe_stages(sub)

    def _sign(self, sub: Submission, query, conf) -> Dict:
        """Text to DataFrame (its ``plan.parse``/``plan.analyze`` intervals
        join the submission's), signature, fingerprints, conf digest and
        the result cache's key."""
        df = self._build_df(query)
        if isinstance(query, str):
            sub.spans.extend(df._planned)
        plan = df._plan
        sig = plan_signature(plan)
        cdig = self._conf_digest(conf)
        rkey = None
        if sig is not None:
            rkey = hashlib.sha1(
                (cdig + ":" + sig.exact).encode()).hexdigest()
        return {"plan": plan, "sig": sig, "fps": plan_fingerprints(plan),
                "cdig": cdig, "rkey": rkey}

    def _probe_result_cache(self, sub: Submission, query) -> Dict:
        """Builds the plan, signs it, and probes the result cache under
        the CURRENT conf.  The probe (plan/signature/digest) is handed
        to ``_execute`` so an admitted miss does not re-plan unless the
        online tuner changed the conf while the query waited."""
        from spark_rapids_tpu.aux.tracing import timed_span
        with timed_span("serve.lookup", sub.spans, cache="result"):
            probe = self._sign(sub, query, self.conf)
            probe["cached"] = self.result_cache.lookup(probe["rkey"],
                                                       probe["fps"])
        return probe

    def _observe_stages(self, sub: Submission) -> None:
        """End-of-submission latency decomposition: every stage (and the
        end-to-end latency) observes into the process-wide histograms
        rendered by render_prometheus(), and the per-stage sums ride a
        ``servingAdmission`` op="complete" event."""
        stages = sub.info.get("stages") or {}
        e2e = float(sub.info.get("latency_s", 0.0) or 0.0)
        observe_latency("e2e", e2e)
        for k in STAGE_KEYS:
            observe_latency(k[:-2], float(stages.get(k, 0.0) or 0.0))
        EV.emit("servingAdmission", op="complete", serve_id=sub.serve_id,
                latency_s=round(e2e, 6),
                resolved=str(sub.info.get("resolved", "")),
                error=sub.error is not None,
                **{k: round(float(stages.get(k, 0.0) or 0.0), 6)
                   for k in STAGE_KEYS})

    def _execute(self, sub: Submission, query, conf, probe=None):
        from spark_rapids_tpu.aux.tracing import (note, query_scope,
                                                  timed_span)
        from spark_rapids_tpu.serving.signature import plan_pins
        from spark_rapids_tpu.session import collect_with_speculation
        stages = sub.info["stages"]
        with timed_span("serve.lookup", sub.spans, cache="result"):
            if probe is None or probe["cdig"] != self._conf_digest(conf):
                # the online tuner changed the conf while the query
                # waited: plan and sign again under the new one
                probe = self._sign(sub, query, conf)
            # else the pre-admission probe is still valid: reuse its
            # plan/signature and re-check only the cache (a concurrent
            # peer may have published this result while we waited for
            # admission)
            plan, sig, fps = probe["plan"], probe["sig"], probe["fps"]
            cdig, rkey = probe["cdig"], probe["rkey"]
            cached = self.result_cache.lookup(rkey, fps)
        if cached is not None:
            sub.info["resolved"] = "result_cache"
            return cached
        # what closed before the query opens, for it to adopt
        planned = tuple(sub.spans)
        lease_box: Dict = {}

        def prepared_plan():
            from spark_rapids_tpu.aux.metrics import (MetricLevel,
                                                      instrument_plan)
            from spark_rapids_tpu.exec.basic import refresh_cte_epochs
            from spark_rapids_tpu.plan.overrides import TpuOverrides
            if "lease" not in lease_box:
                with timed_span("serve.lookup", sub.spans, cache="plan"):
                    lease = self.plan_cache.lookup(cdig, sig, fps)
                if lease is not None:
                    # cached physical plan: NO planning, NO compile —
                    # just the per-execution preamble (fresh CTE epoch,
                    # metric reset; instrument_plan is idempotent)
                    sub.info["resolved"] = "plan_cache"
                    refresh_cte_epochs(lease.plan)
                    instrument_plan(lease.plan, MetricLevel.parse(
                        conf.get(C.METRICS_LEVEL.key, "MODERATE")))
                else:
                    sub.info["resolved"] = "planned"
                    executed = TpuOverrides(conf).apply(plan)
                    lease = self.plan_cache.insert(cdig, sig, fps,
                                                   executed)
                    if lease is None:       # cache disabled / unsigned
                        lease_box["plan"] = executed
                lease_box["lease"] = lease
                note(resolved=sub.info["resolved"])
            else:
                # speculation-overflow replay: exec nodes memoize
                # per-execution state (exchange stores, join build
                # caches) that the FAILED speculative pass poisoned
                # with truncated batches — an exact-mode replay must
                # never reuse it.  Re-plan fresh instances (the rare
                # path; the DataFrame action path re-plans per replay
                # for the same reason) and swap the rebuilt plan into
                # the cache so later hits never see the poisoned ones.
                executed = TpuOverrides(conf).apply(plan)
                lease = lease_box["lease"]
                if lease is not None:
                    lease._variant.plan = executed
                else:
                    lease_box["plan"] = executed
            lease = lease_box["lease"]
            out = lease.plan if lease is not None else lease_box["plan"]
            q = EV.active_query()
            if q is not None:
                q.attach_plan(out)
            return out

        prepared_s = 0.0    # inside prepared_plan, over the replays

        def timed_prepared_plan():
            # plan_s accumulates across speculation replays (the rare
            # re-plan path invokes this more than once); the plan-cache
            # lookup inside it is ``lookup_s``'s, with its span
            nonlocal prepared_s
            t, n = time.monotonic(), len(sub.spans)
            try:
                return prepared_plan()
            finally:
                dt = time.monotonic() - t
                looked = sum(end - start for _, start, end in sub.spans[n:])
                prepared_s += dt
                stages["plan_s"] = round(stages["plan_s"] + dt - looked, 6)

        t_exec = time.monotonic()
        qe = None
        try:
            with query_scope(conf, f"serve:{sub.tag}", planned) as qe:
                batch = collect_with_speculation(conf,
                                                 timed_prepared_plan)
        except BaseException:
            # a FAILED execution may leave the plan's exec instances
            # with poisoned memoized state (a speculative pass that
            # died before its overflow check can have materialized
            # exchange stores from truncated joins) — the variant must
            # never serve a later exact hit.  Discard drops it from the
            # cache and releases the lease
            lease = lease_box.pop("lease", None)
            if lease is not None:
                self.plan_cache.discard(lease)
            raise
        finally:
            lease = lease_box.get("lease")
            if lease is not None:
                lease.release()
        # decompose the execution wall: compile and collect are what THIS
        # query's summary counted (its own compiles; its own D2H fetch
        # seconds in the transition ledger: a peer's are in the peer's),
        # execute is the clamped remainder.  With tracing off
        # (``spark.rapids.sql.tracing.enabled``) no query opened and the
        # remainder is all there is
        done = (qe.summary_dict if qe is not None else None) or {}
        compile_s = float(done.get("compile_s", 0.0))
        collect_s = float(done.get("transitions", {}).get("d2h_s", 0.0))
        stages["compile_s"] = round(compile_s, 6)
        stages["collect_s"] = round(collect_s, 6)
        stages["execute_s"] = round(
            max(0.0, time.monotonic() - t_exec - prepared_s - compile_s
                - collect_s), 6)
        self.result_cache.put(rkey, fps, batch, pins=plan_pins(plan))
        if self.autotune_enabled and qe is not None:
            self._autotune_step(qe)
        return batch

    # -- online tuning loop --------------------------------------------------
    def _autotune_step(self, qe) -> None:
        """Between queries: evaluate the rule set over this query's live
        event ring + the sampler's resourceSample feed; apply accepted
        allowlisted deltas to the NEXT admitted query."""
        try:
            recs = self._evaluate_rules(qe)
        except Exception:   # noqa: BLE001 - tuning must never fail a query
            return
        for rec in recs:
            if rec.key not in ONLINE_TUNABLE_KEYS:
                continue
            self._apply_delta(rec, qe.query_id)

    def _evaluate_rules(self, qe) -> List:
        from spark_rapids_tpu.tools.autotune import autotune_query
        from spark_rapids_tpu.tools.reader import (ReadDiagnostics,
                                                   profiles_from_events)
        # the live feed: sampler events (global ring, NO_QUERY) first so
        # the reader buckets them as the run's sample stream, then the
        # query's own ring (spanMetrics/queryEnd included — finish ran)
        samples = [e for e in self._global_ring.events()
                   if e.kind == "resourceSample"]
        events = samples + qe.events()
        profiles, _ = profiles_from_events(events, ReadDiagnostics())
        prof = next((p for p in profiles if p.query_id == qe.query_id),
                    None)
        if prof is None:
            return []
        if not prof.conf:
            prof.conf = dict(qe.conf_snapshot or {})
        return autotune_query(prof)

    def _apply_delta(self, rec, query_id: int) -> None:
        from spark_rapids_tpu.config import TpuConf
        with self._conf_lock:
            current = self._conf.get(rec.key)
            if str(current) == str(rec.recommended):
                return
            try:
                new_conf = self._conf.set(rec.key, str(rec.recommended))
            except Exception:   # noqa: BLE001 - a rec failing validation
                return          # is dropped, never fatal
            self._conf = new_conf
        if rec.key == "spark.rapids.sql.concurrentGpuTasks":
            # the permit budget lives in the RUNTIME semaphore: apply
            # online (grows wake waiters; shrinks drain as tasks finish)
            from spark_rapids_tpu.memory.device_manager import get_runtime
            rt = get_runtime()
            if rt is not None:
                rt.semaphore.resize(int(rec.recommended))
        self.autotune_applied.append(
            (rec.key, current, rec.recommended, rec.reason, query_id))
        EV.emit("autotuneApplied", key=rec.key, old=str(current),
                new=str(rec.recommended), query_id=query_id,
                reason=rec.reason[:160])
