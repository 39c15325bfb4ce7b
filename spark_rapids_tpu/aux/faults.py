"""Process-wide fault-injection framework + recovery bookkeeping.

Generalizes the ``RmmSpark.forceRetryOOM`` pattern (memory/retry.py's
thread-local injection counters) into ONE mechanism every layer shares:
a registry of *named fault points* with deterministic arm/fire semantics.

Reference: the RmmSpark JNI state machine injects OOMs at allocation
points (tests/.../RmmSparkRetrySuiteBase.scala:27-53); the plugin's
shuffle suites script peer loss through mocked transports.  Here the
same discipline covers every data-movement layer:

- ``memory.alloc``      tracked allocation points (memory/retry.py)
- ``shuffle.fetch``     client fetch attempts (shuffle/client_server.py)
- ``shuffle.send``      server block sends (shuffle/client_server.py)
- ``shuffle.connect``   transport connection setup (socket_transport.py)
- ``task.run``          task start in the parallel runner (plan/base.py)
- ``parallel.collective``  mesh collective shuffle (parallel/collective.py)
- ``pipeline.prefetch`` prefetch-spool start (exec/pipeline.py producer)
- ``memory.block``      allocation admission (memory/catalog.py reserve):
                        an injected never-releasing hold the watchdog
                        must detect, dump and cancel
- ``watchdog.sweep``    inside the watchdog sweep (memory/arbiter.py):
                        the daemon must survive a faulted pass

Semantics (mirroring ``force_retry_oom(num_ooms, skip)``): arming a point
with ``n`` and ``skip`` makes the next ``skip`` triggers pass and the
``n`` after that raise.  Deterministic — no randomness, no wall clock —
so chaos tests assert bit-identical results and exact event counts.

Conf-driven arming rides ``spark.rapids.chaos.*`` keys (value ``"n"`` or
``"n:skip"``); ``TpuOverrides.apply``/``TpuSession.set_conf`` re-arm on
every query so each action sees a fresh fault budget.

The module also keeps process-wide *recovery counters* (fetch retries,
failovers, task retries, breaker trips, map re-runs, worker expiries):
every recovery emit site notes its transition here so a run can report
what recovery cost without scraping event logs.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple


class InjectedFault(Exception):
    """Default exception an armed fault point raises (task/exec layers).
    Classified retryable: the task runner re-attempts work that dies of
    one, exactly like a real transient executor failure."""


@dataclasses.dataclass
class _ArmedFault:
    remaining: int          # faults still to fire
    skip: int               # triggers to let pass first
    exc_factory: Callable[[str], BaseException]
    fired: int = 0          # total faults this arming has raised


_LOCK = threading.Lock()
_ARMED: Dict[str, _ArmedFault] = {}
#: lifetime fire counts per point (survive disarm; bench/test introspection)
_FIRED_TOTAL: Dict[str, int] = {}

#: recovery-transition counters (emit sites call note_recovery)
_RECOVERY: Dict[str, int] = {}


def _default_exc(point: str) -> BaseException:
    return InjectedFault(f"injected fault at {point!r}")


def arm_fault(point: str, n: int = 1, skip: int = 0,
              exc: Optional[Callable[[str], BaseException]] = None) -> None:
    """Arms ``point`` to raise on its next ``n`` triggers after letting
    ``skip`` pass (reference: RmmSpark.forceRetryOOM(num_ooms, skip)).
    ``exc`` is a callable ``point -> exception``; defaults per layer are
    applied by the trigger site via ``maybe_fire``'s armed state."""
    if n <= 0:
        disarm(point)
        return
    with _LOCK:
        _ARMED[point] = _ArmedFault(int(n), max(0, int(skip)),
                                    exc or _default_exc)


def disarm(point: str) -> None:
    with _LOCK:
        _ARMED.pop(point, None)


def disarm_all() -> None:
    with _LOCK:
        _ARMED.clear()


def maybe_fire(point: str) -> None:
    """Called at a fault point: no-op unless armed.  Zero-cost when the
    chaos layer is idle (one dict lookup under no lock)."""
    if not _ARMED:        # benign race: arming is test/chaos-conf driven
        return
    with _LOCK:
        st = _ARMED.get(point)
        if st is None:
            return
        if st.skip > 0:
            st.skip -= 1
            return
        st.remaining -= 1
        st.fired += 1
        _FIRED_TOTAL[point] = _FIRED_TOTAL.get(point, 0) + 1
        if st.remaining <= 0:
            del _ARMED[point]
        exc = st.exc_factory(point)
    from spark_rapids_tpu.aux.events import emit
    emit("faultInjected", point=point, exc=type(exc).__name__)
    raise exc


def is_armed(point: str) -> bool:
    with _LOCK:
        return point in _ARMED


def fault_stats() -> Dict[str, int]:
    """Lifetime fault fire counts per point."""
    with _LOCK:
        return dict(_FIRED_TOTAL)


def reset_fault_stats() -> None:
    with _LOCK:
        _FIRED_TOTAL.clear()


# ---------------------------------------------------------------------------
# recovery counters (the "what did resilience cost" ledger)
# ---------------------------------------------------------------------------

#: THE recovery vocabulary: event kind -> ledger/summary key.  Emit sites
#: pair each event with note_recovery(key); tracing's per-query summary
#: and tools/profile's recovery bucket both derive from this map, so
#: adding a recovery kind here propagates to every surface.
RECOVERY_KINDS: Dict[str, str] = {
    "fetchRetry": "fetch_retries",
    "fetchFailover": "fetch_failovers",
    "taskRetry": "task_retries",
    "taskDegraded": "tasks_degraded",
    "breakerTrip": "breaker_trips",
    "mapRerun": "map_reruns",
    "workerExpired": "workers_expired",
    "collectiveFallback": "collective_fallbacks",
    "faultInjected": "faults_injected",
    "deadlockBreak": "deadlock_breaks",
    "taskCancelled": "tasks_cancelled",
    "watchdogDump": "watchdog_dumps",
}


def note_recovery(kind: str, n: int = 1) -> None:
    """Recovery emit sites (fetchRetry, taskRetry, ...) tally here so a
    whole bench run's recovery overhead is one snapshot away."""
    with _LOCK:
        _RECOVERY[kind] = _RECOVERY.get(kind, 0) + n


def recovery_stats() -> Dict[str, int]:
    with _LOCK:
        return dict(_RECOVERY)


def reset_recovery_stats() -> None:
    with _LOCK:
        _RECOVERY.clear()


# ---------------------------------------------------------------------------
# conf-driven arming (spark.rapids.chaos.*)
# ---------------------------------------------------------------------------

def parse_chaos_spec(spec: str) -> Optional[Tuple[int, int]]:
    """``"n"`` or ``"n:skip"`` -> (n, skip); empty/0 -> None (disarmed).
    Raises ValueError on malformed specs (set_conf-time validation)."""
    s = str(spec).strip()
    if not s or s.lower() in ("0", "false", "off", "none"):
        return None
    parts = s.split(":")
    if len(parts) > 2:
        raise ValueError(f"chaos spec {spec!r}: want 'n' or 'n:skip'")
    n = int(parts[0])
    skip = int(parts[1]) if len(parts) == 2 else 0
    if n < 0 or skip < 0:
        raise ValueError(f"chaos spec {spec!r}: negative counts")
    return (n, skip) if n else None


def chaos_spec_ok(spec: str) -> bool:
    """Conf checker form of ``parse_chaos_spec``."""
    try:
        parse_chaos_spec(spec)
        return True
    except (ValueError, TypeError):
        return False


def _retry_oom(point: str) -> BaseException:
    from spark_rapids_tpu.memory.retry import RetryOOM
    return RetryOOM(f"injected RetryOOM at {point!r}")


def _conn_error(point: str) -> BaseException:
    return ConnectionError(f"injected connection fault at {point!r}")


def _block_hold(point: str) -> BaseException:
    from spark_rapids_tpu.memory.arbiter import InjectedBlockHold
    return InjectedBlockHold(f"injected allocation hold at {point!r}")


#: chaos conf key suffix -> (fault point, exception factory)
CHAOS_POINTS: Dict[str, Tuple[str, Callable[[str], BaseException]]] = {
    "shuffle.fetch": ("shuffle.fetch", _conn_error),
    "shuffle.send": ("shuffle.send", _conn_error),
    "shuffle.connect": ("shuffle.connect", _conn_error),
    "task.run": ("task.run", _default_exc),
    "parallel.collective": ("parallel.collective", _default_exc),
    "memory.alloc": ("memory.alloc", _retry_oom),
    "pipeline.prefetch": ("pipeline.prefetch", _default_exc),
    "memory.block": ("memory.block", _block_hold),
    "watchdog.sweep": ("watchdog.sweep", _default_exc),
}

_CHAOS_PREFIX = "spark.rapids.chaos."


def arm_from_conf(conf) -> List[str]:
    """Syncs the armed set with the conf's ``spark.rapids.chaos.*`` keys:
    a set spec arms its point, an empty spec disarms it (a pooled thread
    must not inherit a previous session's chaos).  Returns armed points."""
    armed: List[str] = []
    for suffix, (point, exc) in CHAOS_POINTS.items():
        spec = conf.get(_CHAOS_PREFIX + suffix, "")
        parsed = parse_chaos_spec(spec) if spec else None
        if parsed is None:
            disarm(point)
        else:
            n, skip = parsed
            arm_fault(point, n, skip, exc)
            armed.append(point)
    return armed


# ---------------------------------------------------------------------------
# circuit breaker (stage-scoped degradation)
# ---------------------------------------------------------------------------

class CircuitBreaker:
    """Counts faults toward a threshold; once tripped, callers degrade to
    their safe path instead of burning retries (the task runner drops to
    single-threaded inline execution for the rest of the stage).

    ``threshold <= 0`` disables (never trips)."""

    def __init__(self, threshold: int, name: str = "stage"):
        self.threshold = int(threshold)
        self.name = name
        self._failures = 0
        self._tripped = False
        self._lock = threading.Lock()

    @property
    def failures(self) -> int:
        with self._lock:
            return self._failures

    @property
    def tripped(self) -> bool:
        with self._lock:
            return self._tripped

    def record_failure(self) -> bool:
        """Returns True exactly once: on the failure that trips it."""
        if self.threshold <= 0:
            return False
        with self._lock:
            self._failures += 1
            if not self._tripped and self._failures >= self.threshold:
                self._tripped = True
                just_tripped = True
            else:
                just_tripped = False
        if just_tripped:
            note_recovery("breaker_trips")
            from spark_rapids_tpu.aux.events import emit
            emit("breakerTrip", name=self.name, failures=self._failures,
                 threshold=self.threshold)
        return just_tripped
