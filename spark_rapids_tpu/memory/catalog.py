"""Tiered buffer catalog: DEVICE(HBM) -> HOST(DRAM) -> DISK with spill.

Reference: ``RapidsBufferCatalog.scala`` (:62 class, :737 object; handle API
:47,126,215), ``RapidsBufferStore.scala`` (:58 spill logic),
``RapidsDeviceMemoryStore.scala`` / ``RapidsHostMemoryStore.scala`` /
``RapidsDiskStore.scala``, ``SpillPriorities.scala`` (:26), and
``DeviceMemoryEventHandler.scala`` (:36-193 spill-on-alloc-failure).

TPU-first: XLA owns physical HBM, so the device store is an accounting layer
over catalog-tracked jax buffers.  ``reserve()`` is the admission point every
operator calls before materializing a large result; on budget exhaustion it
synchronously spills lowest-priority buffers (the reference's event handler
does this inside the RMM callback).  If spilling wasn't enough, a registered
task thread PARKS in the resource arbiter (``memory/arbiter.py`` —
BLOCKED_ON_ALLOC on a condition variable signalled by every ``remove``/
spill) instead of raising ``RetryOOM`` immediately: concurrent tasks
cooperate, and only a detected deadlock (or the MAX_BLOCK_MS backstop)
surfaces a forced Retry/SplitAndRetry OOM toward the task's retry frame.
Unregistered threads (direct-catalog tests, driver code) keep the original
raise-immediately behavior.
"""

from __future__ import annotations

import enum
import itertools
import os
import tempfile
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch
from spark_rapids_tpu.memory import arbiter as _ARB
from spark_rapids_tpu.memory.retry import RetryOOM, maybe_inject_oom, task_context


#: codec for host->disk spill files (spark.rapids.memory.spill.codec,
#: synced by TpuOverrides.apply): the shuffle serializer's frame format,
#: so the spill tier rides the same lz4/zlib path shuffle payloads do
#: (reference: nvcomp-compressed spill in RapidsDiskStore)
SPILL_CODEC = "lz4"


class StorageTier(enum.IntEnum):
    """reference: RapidsBuffer.scala:59-64 StorageTier"""
    DEVICE = 0
    HOST = 1
    DISK = 2


class SpillPriority:
    """Lower value spills first (reference: SpillPriorities.scala:26)."""
    INPUT_FROM_SHUFFLE = -100
    ACTIVE_BATCHING = 0
    ACTIVE_ON_DECK = 100
    HOST_MEMORY = -50


_handle_ids = itertools.count(1)


class BufferHandle:
    """Opaque handle to a catalog buffer (reference: RapidsBufferHandle)."""

    __slots__ = ("id", "priority", "closed")

    def __init__(self, priority: int):
        self.id = next(_handle_ids)
        self.priority = priority
        self.closed = False

    def __repr__(self):
        return f"BufferHandle(id={self.id}, prio={self.priority})"


class _Buffer:
    __slots__ = ("handle", "tier", "device_batch", "host_batch", "disk_path",
                 "device_nbytes", "host_nbytes", "disk_nbytes",
                 "disk_logical_nbytes", "spillable", "owned",
                 "query_id", "span_id")

    def __init__(self, handle: BufferHandle):
        self.handle = handle
        #: attribution tags stamped at registration from the emitting
        #: thread's query/span context (aux/events.py); -1 outside any
        #: query.  The console /memory endpoint aggregates bytes by
        #: these through ``attribution()``.
        self.query_id = -1
        self.span_id = -1
        self.tier = StorageTier.DEVICE
        self.device_batch: Optional[ColumnarBatch] = None
        self.host_batch: Optional[HostColumnarBatch] = None
        self.disk_path: Optional[str] = None
        self.device_nbytes = 0
        self.host_nbytes = 0
        #: actual on-disk (post-codec) size — the accounting the pool
        #: watermarks and spill events report; re-statting the file
        #: raced with unlink and silently leaked disk_bytes on loss
        self.disk_nbytes = 0
        self.disk_logical_nbytes = 0
        self.spillable = True
        #: True = the catalog exclusively owns the device arrays and may
        #: .delete() them on spill/remove.  False = the arrays may be
        #: shared with other holders (scan device caches, exchange
        #: stores, a consumer using the batch right now): spill/remove
        #: only DROP the catalog's reference — HBM frees when the last
        #: Python reference does.  In-flight pipeline prefetch registers
        #: this way (exec/pipeline.py).
        self.owned = True


def _attribution_tags() -> tuple:
    """(query_id, span_id) of the registering thread's context, -1/-1
    outside any query.  Contextvar + thread-local reads only — no lock,
    negligible cost on the registration path."""
    from spark_rapids_tpu.aux import events as EV
    q = EV.active_query()
    if q is None:
        return -1, -1
    sid = EV.current_span_id()
    return q.query_id, (sid if sid is not None else -1)


def _delete_device_batch(batch: ColumnarBatch) -> None:
    """Releases device buffers eagerly (reference: RapidsBuffer.free /
    cudf close; jax arrays support explicit .delete()), through the
    gateway, whose gap ledger asks arrays whether they are ready."""
    from spark_rapids_tpu.aux.transitions import delete_arrays
    # run_ends: RleColumn's extra plane; DICTIONARY value planes are
    # shared process-wide and must never be deleted with a batch
    delete_arrays([arr for col in batch.columns
                   for arr in (col.data, col.validity, col.lengths,
                               getattr(col, "run_ends", None))])


class BufferCatalog:
    """Central registry of spillable buffers across storage tiers."""

    def __init__(self, device_limit_bytes: int, host_limit_bytes: int,
                 disk_dir: Optional[str] = None, debug: bool = False):
        self.device_limit = device_limit_bytes
        self.host_limit = host_limit_bytes
        self._disk_dir = disk_dir
        self._buffers: Dict[int, _Buffer] = {}
        from spark_rapids_tpu.aux.lockorder import tracked_rlock
        self._lock = tracked_rlock("catalog")
        self.device_bytes = 0
        #: high-watermark of device_bytes (resource sampler / Prometheus)
        self.device_peak_bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        #: pre-codec bytes behind disk_bytes (compression ratio =
        #: disk_logical_bytes / disk_bytes)
        self.disk_logical_bytes = 0
        self.spill_count = 0
        self.debug = debug

    # -- admission ----------------------------------------------------------
    def reserve(self, nbytes: int) -> None:
        """Admission check before materializing ``nbytes`` on device.

        Mirrors DeviceMemoryEventHandler: on shortfall, synchronously spill
        spillable device buffers.  Still short, a registered task thread
        blocks in the arbiter until concurrent tasks release memory (a
        detected deadlock wakes one victim with a forced OOM); an
        unregistered thread — or an expired MAX_BLOCK_MS wait — signals
        RetryOOM so the calling retry frame can spill/split as before.
        """
        maybe_inject_oom()
        from spark_rapids_tpu.aux.faults import maybe_fire
        try:
            # chaos point memory.block: an injected never-releasing
            # allocation hold (only watchdog cancellation breaks it)
            maybe_fire("memory.block")
        except _ARB.InjectedBlockHold:
            _ARB.get_arbiter().hold_until_cancelled()
        blocked = False
        arb = _ARB.get_arbiter()
        while True:
            with self._lock:
                if self.device_bytes + nbytes <= self.device_limit:
                    if blocked or arb.is_bufn():
                        break       # cooperation worked: note outside lock
                    return
                needed = self.device_bytes + nbytes - self.device_limit
                freed = self._spill_device_locked(needed)
                if self.device_bytes + nbytes <= self.device_limit:
                    if blocked or arb.is_bufn():
                        break
                    return
                used = self.device_bytes
                # sampled under the catalog lock AFTER the failed
                # re-check: every byte-freeing release serializes behind
                # this lock, so a release the park could miss must bump
                # the seq past this sample and block_on_alloc retries
                # immediately — while our OWN spill above is already
                # reflected, so it cannot self-invalidate the park.
                # (lock order catalog -> arbiter, one-directional.)
                seq0 = arb.release_seq()
            outcome = arb.block_on_alloc(nbytes, seen_seq=seq0) \
                if arb.can_block() else "unregistered"
            if outcome == "retry":
                blocked = True
                continue    # released bytes: re-try admission (re-spill)
            # unregistered thread / MAX_BLOCK_MS expired: the pre-arbiter
            # behavior — signal the retry frame (forced OOMs and
            # cancellation raise out of block_on_alloc directly)
            mt = task_context().metrics
            if mt is not None:
                mt.oom_count += 1
            from spark_rapids_tpu.aux.events import emit
            emit("oom", needed=nbytes, used=used,
                 limit=self.device_limit, freed=freed)
            raise RetryOOM(
                f"device pool exhausted: need {nbytes}, used {used}"
                f"/{self.device_limit}, freed only {freed}")
        arb.note_alloc_success(task_context().task_id)

    # -- registration -------------------------------------------------------
    def add_device_batch(self, batch: ColumnarBatch,
                         priority: int = SpillPriority.ACTIVE_BATCHING,
                         spillable: bool = True,
                         owned: bool = True) -> BufferHandle:
        nbytes = batch.nbytes()
        self.reserve(nbytes)
        qid, sid = _attribution_tags()
        with self._lock:
            handle = BufferHandle(priority)
            buf = _Buffer(handle)
            buf.query_id, buf.span_id = qid, sid
            buf.device_batch = batch
            buf.device_nbytes = nbytes
            buf.spillable = spillable
            buf.owned = owned
            buf.tier = StorageTier.DEVICE
            self._buffers[handle.id] = buf
            self.device_bytes += nbytes
            self.device_peak_bytes = max(self.device_peak_bytes,
                                         self.device_bytes)
        # victim-selection input: the owning task's most-evictable buffer
        _ARB.get_arbiter().note_buffer_priority(task_context().task_id,
                                                priority)
        return handle

    def add_host_batch(self, batch: HostColumnarBatch,
                       priority: int = SpillPriority.HOST_MEMORY) -> BufferHandle:
        qid, sid = _attribution_tags()
        with self._lock:
            handle = BufferHandle(priority)
            buf = _Buffer(handle)
            buf.query_id, buf.span_id = qid, sid
            buf.host_batch = batch
            buf.host_nbytes = batch.nbytes()
            buf.tier = StorageTier.HOST
            self._buffers[handle.id] = buf
            self.host_bytes += buf.host_nbytes
            self._maybe_spill_host_locked()
            return handle

    # -- retrieval (unspill on demand) --------------------------------------
    def get_device_batch(self, handle: BufferHandle) -> ColumnarBatch:
        with self._lock:
            buf = self._require(handle)
            if buf.tier == StorageTier.DEVICE:
                return buf.device_batch
            host = self._host_batch_locked(buf)
        # admission BEFORE materializing on device (the estimate is exact for
        # fixed-width data and a safe upper bound for strings: pow2 bucket
        # padding is < 2x the host payload + validity/length vectors)
        est = 2 * host.nbytes() + 16 * max(host.row_count, 1024)
        self.reserve(est)
        t0 = time.monotonic()
        dev = host.to_device()
        unspill_s = time.monotonic() - t0
        nbytes = dev.nbytes()
        promoted = False
        with self._lock:
            buf = self._buffers.get(handle.id)
            if buf is None:  # removed concurrently
                _delete_device_batch(dev)
                raise KeyError(f"unknown or closed buffer handle {handle}")
            if buf.tier != StorageTier.DEVICE:
                buf.device_batch = dev
                buf.device_nbytes = nbytes
                self.device_bytes += nbytes
                self.device_peak_bytes = max(self.device_peak_bytes,
                                             self.device_bytes)
                # single-tier ownership: promotion drops the host copy and its
                # charge (prevents double-count on the next spill cycle)
                if buf.host_batch is not None:
                    self.host_bytes -= buf.host_nbytes
                    buf.host_batch = None
                    buf.host_nbytes = 0
                buf.tier = StorageTier.DEVICE
                promoted = True
            else:
                _delete_device_batch(dev)  # raced with another unspiller
            out = buf.device_batch
        if promoted:
            # exactly one event per actual promotion (race losers skip);
            # emitted outside the lock
            from spark_rapids_tpu.aux.events import emit
            emit("unspill", bytes=nbytes, rows=host.row_count,
                 buffer_id=handle.id, duration_s=round(unspill_s, 6))
        return out

    def get_host_batch(self, handle: BufferHandle) -> HostColumnarBatch:
        with self._lock:
            buf = self._require(handle)
            if buf.tier == StorageTier.DEVICE:
                return buf.device_batch.to_host()
            return self._host_batch_locked(buf)

    def tier_of(self, handle: BufferHandle) -> StorageTier:
        with self._lock:
            return self._require(handle).tier

    def set_spillable(self, handle: BufferHandle, spillable: bool) -> None:
        with self._lock:
            self._require(handle).spillable = spillable

    def disown(self, handle: BufferHandle) -> None:
        """Transfers device-array ownership back to the caller: any later
        spill/remove of this buffer drops the catalog's reference instead
        of deleting the arrays (SpillableColumnarBatch.release unwrap)."""
        with self._lock:
            buf = self._buffers.get(handle.id)
            if buf is not None:
                buf.owned = False

    def remove(self, handle: BufferHandle) -> None:
        freed_device = False
        with self._lock:
            buf = self._buffers.pop(handle.id, None)
            handle.closed = True
            if buf is None:
                return
            if buf.device_batch is not None:
                self.device_bytes -= buf.device_nbytes
                freed_device = buf.device_nbytes > 0
                if buf.owned:
                    _delete_device_batch(buf.device_batch)
            if buf.host_batch is not None:
                self.host_bytes -= buf.host_nbytes
            if buf.disk_path is not None:
                # recorded size, not a re-stat: the decrement must happen
                # even when the file is already gone
                self.disk_bytes -= buf.disk_nbytes
                self.disk_logical_bytes -= buf.disk_logical_nbytes
                try:
                    os.unlink(buf.disk_path)
                except OSError:
                    pass
        if freed_device:
            # wake BLOCKED_ON_ALLOC parkers: admission may now fit
            _ARB.get_arbiter().notify_release()

    # -- spilling -----------------------------------------------------------
    def synchronous_spill(self, target_free_bytes: Optional[int]) -> int:
        """Spills device buffers until ``target_free_bytes`` are free (None =
        spill everything spillable).  Returns bytes freed."""
        with self._lock:
            if target_free_bytes is None:
                needed = self.device_bytes
            else:
                free = self.device_limit - self.device_bytes
                needed = max(0, target_free_bytes - free)
            return self._spill_device_locked(needed)

    def _spill_device_locked(self, needed: int) -> int:
        candidates = sorted(
            (b for b in self._buffers.values()
             if b.tier == StorageTier.DEVICE and b.spillable),
            key=lambda b: b.handle.priority)
        freed = 0
        mt = task_context().metrics
        for buf in candidates:
            if freed >= needed:
                break
            t0 = time.monotonic()
            host = buf.device_batch.to_host()
            spill_s = time.monotonic() - t0
            if buf.owned:
                _delete_device_batch(buf.device_batch)
            self.device_bytes -= buf.device_nbytes
            freed += buf.device_nbytes
            buf.device_batch = None
            buf.device_nbytes = 0
            buf.host_batch = host
            buf.host_nbytes = host.nbytes()
            self.host_bytes += buf.host_nbytes
            buf.tier = StorageTier.HOST
            self.spill_count += 1
            if mt is not None:
                mt.spill_count += 1
                mt.spill_bytes += buf.host_nbytes
            from spark_rapids_tpu.aux.events import emit
            emit("spill", tier="device->host", bytes=buf.host_nbytes,
                 buffer_id=buf.handle.id, priority=buf.handle.priority,
                 duration_s=round(spill_s, 6))
        self._maybe_spill_host_locked()
        if freed > 0:
            # device bytes moved down a tier: alloc parkers re-try
            _ARB.get_arbiter().notify_release()
        return freed

    def _maybe_spill_host_locked(self) -> None:
        if self.host_bytes <= self.host_limit:
            return
        candidates = sorted(
            (b for b in self._buffers.values()
             if b.tier == StorageTier.HOST and b.spillable),
            key=lambda b: b.handle.priority)
        for buf in candidates:
            if self.host_bytes <= self.host_limit:
                break
            self._spill_host_to_disk_locked(buf)

    def _spill_host_to_disk_locked(self, buf: _Buffer) -> None:
        from spark_rapids_tpu.shuffle.serializer import serialize_batch
        d = self._disk_dir or tempfile.gettempdir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"spill-{buf.handle.id}.spill")
        t0 = time.monotonic()
        logical = buf.host_nbytes
        # the shuffle wire format (arrow IPC stream + codec frame): the
        # spill tier compresses through the same lz4/zlib path shuffle
        # payloads use, multiplying effective disk spill capacity
        frame = serialize_batch(buf.host_batch, SPILL_CODEC)
        with open(path, "wb") as fh:
            fh.write(frame)
        self.host_bytes -= buf.host_nbytes
        buf.host_batch = None
        buf.host_nbytes = 0
        buf.disk_path = path
        buf.disk_nbytes = len(frame)
        buf.disk_logical_nbytes = logical
        self.disk_bytes += buf.disk_nbytes
        self.disk_logical_bytes += logical
        buf.tier = StorageTier.DISK
        self.spill_count += 1
        from spark_rapids_tpu.aux.events import emit
        # bytes = ACTUAL on-disk (compressed) size, so profile spill
        # durations and the AutoTuner pressure rule see real I/O volume
        emit("spill", tier="host->disk", bytes=buf.disk_nbytes,
             logical_bytes=logical, codec=SPILL_CODEC,
             buffer_id=buf.handle.id, priority=buf.handle.priority,
             duration_s=round(time.monotonic() - t0, 6))

    def _host_batch_locked(self, buf: _Buffer) -> HostColumnarBatch:
        if buf.host_batch is not None:
            return buf.host_batch
        assert buf.disk_path is not None, "buffer has no backing storage"
        from spark_rapids_tpu.shuffle.serializer import deserialize_batch
        with open(buf.disk_path, "rb") as fh:
            host = deserialize_batch(fh.read())
        # promote back to host tier
        buf.host_batch = host
        buf.host_nbytes = host.nbytes()
        self.host_bytes += buf.host_nbytes
        self.disk_bytes -= buf.disk_nbytes
        self.disk_logical_bytes -= buf.disk_logical_nbytes
        buf.disk_nbytes = 0
        buf.disk_logical_nbytes = 0
        try:
            os.unlink(buf.disk_path)
        except OSError:
            pass
        buf.disk_path = None
        buf.tier = StorageTier.HOST
        return host

    def _require(self, handle: BufferHandle) -> _Buffer:
        buf = self._buffers.get(handle.id)
        if buf is None:
            raise KeyError(f"unknown or closed buffer handle {handle}")
        return buf

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            spillable = sum(b.device_nbytes for b in self._buffers.values()
                            if b.tier == StorageTier.DEVICE and b.spillable)
            return {
                "device_bytes": self.device_bytes,
                "device_limit": self.device_limit,
                "device_peak_bytes": self.device_peak_bytes,
                "spillable_bytes": spillable,
                "host_bytes": self.host_bytes,
                "host_limit": self.host_limit,
                "disk_bytes": self.disk_bytes,
                "disk_logical_bytes": self.disk_logical_bytes,
                "buffers": len(self._buffers),
                "spill_count": self.spill_count,
            }

    def attribution(self) -> List[dict]:
        """Per-(query, operator-span) byte attribution of live buffers,
        aggregated from the registration tags (console /memory).  One
        row per (query_id, span_id) with per-tier byte sums; query_id
        -1 collects buffers registered outside any query (caches,
        exchange stores).  Snapshot under the catalog lock only."""
        with self._lock:
            agg: Dict[tuple, dict] = {}
            for b in self._buffers.values():
                row = agg.setdefault((b.query_id, b.span_id), {
                    "query_id": b.query_id, "span_id": b.span_id,
                    "buffers": 0, "device_bytes": 0, "host_bytes": 0,
                    "disk_bytes": 0, "spillable_bytes": 0,
                })
                row["buffers"] += 1
                row["device_bytes"] += b.device_nbytes
                row["host_bytes"] += b.host_nbytes
                row["disk_bytes"] += b.disk_nbytes
                if b.tier == StorageTier.DEVICE and b.spillable:
                    row["spillable_bytes"] += b.device_nbytes
            return [agg[k] for k in sorted(agg)]

    def close(self) -> None:
        with self._lock:
            for buf in list(self._buffers.values()):
                self.remove(buf.handle)
