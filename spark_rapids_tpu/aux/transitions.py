"""Host-transition & device-sync ledger: the instrumented gateway.

ROADMAP item 2 (millisecond serving floor) claims the engine's latency
gap is per-batch host round trips, blocking device syncs and unnecessary
D2H at operator boundaries.  This module is the instrument that makes
that claim falsifiable: every H2D upload, D2H download and blocking
device sync in the package routes through here (the ``sync-site`` lint
rule pins the discipline for ``block_until_ready``/``jax.device_get``),
emitting schema-v4 ``hostTransition`` / ``deviceSync`` events and
adding each crossing to two ledgers: the process-lifetime one
(``totals()``) and that of the query whose thread made it
(``QueryExecution.note_transition``, the summary's ``transitions``), so
the summaries of overlapping queries add up to the process's delta.

Reference analog: the plugin wraps every transition operator
(GpuRowToColumnarExec / GpuColumnarToRowExec) in dedicated GPU metrics
and NVTX ranges; Theseus (PAPERS.md) makes data movement the first-class
optimization object.  Semantics:

- **hostTransition** (direction ``h2d``/``d2h``): one per packed batch
  transfer, carrying bytes, the column encoding kinds crossing the
  boundary, plane count and measured duration.  H2D duration is the
  ``device_put`` dispatch wall (the transfer itself may complete
  asynchronously); D2H duration is the true blocking fetch.
- **deviceSync**: one per blocking sync that is NOT a batch transfer —
  deferred-count forces, speculation overflow checks, AQE/exchange count
  fetches — carrying the site label and measured duration.  A D2H batch
  download is a sync too, but it is counted ONCE, as a transition;
  ``sync_count``/``sync_seconds`` cover only the non-transfer syncs.

Conf (``spark.rapids.sql.transitions.*``) syncs through
``sync_from_conf`` at session construction / ``set_conf`` — the same
process-singleton lifecycle as the sampler and lock-order validator.
Disabled, every wrapper degrades to the raw operation (the trimodal
bit-identity test pins that results never change either way).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

import numpy as np

from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux.tracing import span

#: instrumentation master switch + per-boundary event emission switch
#: (module-internal; mutated ONLY by sync_from_conf)
_ENABLED = True
_EVENTS = True

_LOCK = threading.Lock()


@dataclasses.dataclass
class TransitionStats:
    """Process-lifetime ledger counters."""
    h2d_count: int = 0
    h2d_bytes: int = 0
    h2d_seconds: float = 0.0
    d2h_count: int = 0
    d2h_bytes: int = 0
    d2h_seconds: float = 0.0
    sync_count: int = 0
    sync_seconds: float = 0.0


_TOTAL = TransitionStats()


def enabled() -> bool:
    return _ENABLED


def totals() -> dict:
    """Process-lifetime ledger (``render_prometheus()``, the benchmark's
    window deltas)."""
    with _LOCK:
        return {
            "h2d_count": _TOTAL.h2d_count,
            "h2d_bytes": _TOTAL.h2d_bytes,
            "h2d_seconds": round(_TOTAL.h2d_seconds, 6),
            "d2h_count": _TOTAL.d2h_count,
            "d2h_bytes": _TOTAL.d2h_bytes,
            "d2h_seconds": round(_TOTAL.d2h_seconds, 6),
            "sync_count": _TOTAL.sync_count,
            "sync_seconds": round(_TOTAL.sync_seconds, 6),
        }


def sync_from_conf(conf) -> None:
    """Arms/disarms the ledger from ``spark.rapids.sql.transitions.*``
    (called at session construction and from set_conf, like the sampler
    and lock-order singletons).  Counters are never reset — they are
    process-lifetime; only the recording toggles change."""
    global _ENABLED, _EVENTS
    from spark_rapids_tpu import config as C
    _ENABLED = bool(conf.get(C.TRANSITIONS_ENABLED.key, True))
    _EVENTS = bool(conf.get(C.TRANSITIONS_EVENTS.key, True))


# ---------------------------------------------------------------------------
# transition recording (the packed transfer paths call these directly —
# they own the timed operation; columnar/transfer.py)
# ---------------------------------------------------------------------------

def _note_query(door: str, duration_s: float, nbytes: int = 0) -> None:
    q = EV.active_query()
    if q is not None:
        q.note_transition(door, duration_s, int(nbytes))


def record_h2d(nbytes: int, duration_s: float, kinds: str = "",
               planes: int = 0) -> None:
    """One packed host->device upload.  ``kinds`` is the comma-joined
    column encoding-kind set crossing the boundary
    (scalar/string/dec128/array/dict/rle)."""
    if not _ENABLED:
        return
    with _LOCK:
        _TOTAL.h2d_count += 1
        _TOTAL.h2d_bytes += int(nbytes)
        _TOTAL.h2d_seconds += duration_s
    _note_query("h2d", duration_s, nbytes)
    if _EVENTS:
        EV.emit("hostTransition", direction="h2d", bytes=int(nbytes),
                duration_s=round(duration_s, 6), kinds=kinds,
                planes=int(planes))


def record_d2h(nbytes: int, duration_s: float, site: str = "download",
               planes: int = 0) -> None:
    """One packed device->host download (the blocking fetch itself —
    counted as a transition, NOT double-counted as a sync)."""
    if not _ENABLED:
        return
    with _LOCK:
        _TOTAL.d2h_count += 1
        _TOTAL.d2h_bytes += int(nbytes)
        _TOTAL.d2h_seconds += duration_s
    _note_query("d2h", duration_s, nbytes)
    if _EVENTS:
        EV.emit("hostTransition", direction="d2h", bytes=int(nbytes),
                duration_s=round(duration_s, 6), site=site,
                planes=int(planes))


def _record_sync(site: str, duration_s: float,
                 nbytes: Optional[int] = None) -> None:
    with _LOCK:
        _TOTAL.sync_count += 1
        _TOTAL.sync_seconds += duration_s
    _note_query("sync", duration_s)
    if _EVENTS:
        payload = {"site": site, "duration_s": round(duration_s, 6)}
        if nbytes is not None:
            payload["bytes"] = int(nbytes)
        EV.emit("deviceSync", **payload)


# ---------------------------------------------------------------------------
# blocking-sync wrappers (THE sanctioned sync call sites; the sync-site
# lint rule bans raw block_until_ready/jax.device_get elsewhere).  Each
# is one ``xfer.sync`` span over the interval the ledger sums; the two
# transfer doors (``xfer.h2d``/``xfer.d2h``) are opened by
# columnar/transfer.py around the intervals it hands to ``record_h2d`` /
# ``record_d2h``.
# ---------------------------------------------------------------------------

def _sync(site: str, wait, with_bytes: bool = False):
    """One blocking sync: an ``xfer.sync`` span over ``wait()`` and the
    same interval in the ledger."""
    with span("xfer.sync", site=site):
        t0 = time.perf_counter()
        out = wait()
        dt = time.perf_counter() - t0
    _record_sync(site, dt, nbytes=out.nbytes if with_bytes else None)
    return out


def fetch(arr, site: str) -> np.ndarray:
    """Blocking device->host fetch of one array (``np.asarray`` on a
    device array): timed and counted as a deviceSync.  Host inputs pass
    through at numpy cost — safe on either side of the boundary."""
    if not _ENABLED:
        return np.asarray(arr)
    return _sync(site, lambda: np.asarray(arr), with_bytes=True)


def sync_int(x, site: str) -> int:
    """Blocking scalar sync (``int()`` of a 0-d device array — the
    deferred-count force shape)."""
    if not _ENABLED:
        return int(x)
    return _sync(site, lambda: int(x))


def block_until_ready(x, site: str = "dispatch"):
    """Timed ``block_until_ready`` — the dispatch-boundary sync."""
    if not _ENABLED:
        return x.block_until_ready()
    return _sync(site, x.block_until_ready)


def device_get(x, site: str = "device_get"):
    """Timed ``jax.device_get`` — the multi-array blocking fetch."""
    import jax
    if not _ENABLED:
        return jax.device_get(x)
    return _sync(site, lambda: jax.device_get(x))
