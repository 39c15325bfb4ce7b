"""The two cross-query serving caches.

**Plan cache** — normalized-structure -> physical plan.  One entry per
(conf digest, normalized plan structure); literal-promoted queries SHARE
the entry, with one physical-plan variant per literal-value vector (the
compiled-executable set behind those variants is shared anyway: promoted
stages key value-independently in the PR 8 stage compiler, so the second
variant plans but does not compile).  An exact (structure + literals)
repeat skips planning AND compilation entirely.  Variants are LEASED:
one executor at a time may run a cached physical plan (exec nodes carry
per-execution state — CTE caches, shuffle stores); a concurrent
duplicate query simply bypasses the cache and plans fresh, which is
always correct.

**Result cache** — deterministic query/CTE subtree -> result batch,
keyed by (exact plan signature, conf digest) and guarded by the input
file fingerprints.  Bounded in memory; under pressure entries SPILL to
an on-disk arrow tier instead of being lost, and any fingerprint
mismatch (a changed/deleted input file) invalidates.

Both caches publish hit/miss/invalidation counters (the bench payload
reports the plan-cache hit rate) and emit ``planCache`` /
``resultCache`` events so the online tuner and the offline tools see
cache behavior in the same stream as everything else.
"""

from __future__ import annotations

import collections
import os
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

from spark_rapids_tpu.aux.events import emit
from spark_rapids_tpu.aux.tracing import note
from spark_rapids_tpu.plan.base import Exec


class _PlanVariant:
    __slots__ = ("plan", "fingerprints", "lock", "last_used",
                 "lit_values", "key", "nbytes")

    def __init__(self, plan: Exec, fingerprints, lit_values, key=None):
        self.plan = plan
        self.fingerprints = fingerprints
        self.lit_values = lit_values
        self.key = key          # (conf_digest, norm) — discard needs it
        self.lock = threading.Lock()
        self.last_used = time.monotonic()
        self.nbytes = _estimate_plan_bytes(plan)


def _estimate_plan_bytes(plan: Exec) -> int:
    """Shallow retained-size estimate of a physical plan tree: node
    shells + their attribute dicts/values, NOT the data they reference
    (scan partitions / device caches are shared with the session, not
    retained by the cache).  Sizes the planCache.maxBytes bound."""
    import sys
    total = 0
    try:
        for node in plan.collect_nodes():
            total += sys.getsizeof(node)
            d = getattr(node, "__dict__", None)
            if d:
                total += sys.getsizeof(d)
                for v in d.values():
                    total += sys.getsizeof(v)
    except Exception:   # noqa: BLE001 - sizing guess, never fatal
        return 1024
    return max(1, total)


class PlanLease:
    """Checked-out plan-cache variant; release via context manager."""

    def __init__(self, variant: _PlanVariant, kind: str):
        self._variant = variant
        #: "hit" (exact repeat) | "insert" (fresh plan now cached)
        self.kind = kind

    @property
    def plan(self) -> Exec:
        return self._variant.plan

    def __enter__(self) -> "PlanLease":
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def release(self) -> None:
        v, self._variant = self._variant, None
        if v is not None:
            v.last_used = time.monotonic()
            v.lock.release()


class PlanCache:
    """norm-structure -> {literal vector -> leased physical plan}."""

    def __init__(self, max_plans: int = 64, max_bytes: int = 0):
        self.max_plans = int(max_plans)
        #: estimated-byte budget over retained variants, alongside the
        #: count bound — whichever trips first evicts.  0 = unbounded.
        self.max_bytes = int(max_bytes or 0)
        self._lock = threading.Lock()
        #: (conf_digest, norm) -> {lit_values: _PlanVariant}; LRU over
        #: VARIANTS (the leasable unit)
        self._entries: "collections.OrderedDict[Tuple[str, str], Dict]" = \
            collections.OrderedDict()
        #: estimated bytes across retained variants (gauge)
        self.total_bytes = 0
        self.stats = {"hits": 0, "norm_hits": 0, "misses": 0,
                      "busy_bypass": 0, "inserts": 0, "invalidations": 0,
                      "evictions": 0}

    def _variant_count(self) -> int:
        return sum(len(v) for v in self._entries.values())

    def leased_count(self) -> int:
        """Variants currently checked out to an executor (console
        /server)."""
        with self._lock:
            return sum(1 for e in self._entries.values()
                       for v in e.values() if v.lock.locked())

    def lookup(self, conf_digest: str, sig, fingerprints
               ) -> Optional[PlanLease]:
        """Exact-hit lease, or None (miss / busy / stale / disabled).
        A normalized-structure hit with different literal values counts
        as ``norm_hits`` — the caller plans (cheap) but shares the
        entry's compiled-executable set through literal promotion.  The
        outcome is noted on the active query's summary (``plan_cache``:
        ``hit``, ``norm_hit``, ``miss``, ``busy_bypass``,
        ``invalidated``), beside the cache's own totals."""
        if self.max_plans <= 0 or sig is None:
            return None
        key = (conf_digest, sig.norm)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.stats["misses"] += 1
                note(plan_cache="miss")
                emit("planCache", op="miss", norm=sig.norm[:12])
                return None
            self._entries.move_to_end(key)
            variant = entry.get(sig.lit_values)
            if variant is None:
                self.stats["norm_hits"] += 1
                self.stats["misses"] += 1
                note(plan_cache="norm_hit")
                emit("planCache", op="norm_hit", norm=sig.norm[:12],
                     variants=len(entry))
                return None
            if variant.fingerprints != fingerprints:
                # an input file changed under this plan: every variant
                # of the structure scanned the same files — drop them all
                self.stats["invalidations"] += len(entry)
                self.total_bytes -= sum(v.nbytes for v in entry.values())
                del self._entries[key]
                note(plan_cache="invalidated")
                emit("planCache", op="invalidate", norm=sig.norm[:12],
                     variants=len(entry))
                return None
            if not variant.lock.acquire(blocking=False):
                # leased by a concurrent identical query: bypass (exec
                # nodes carry per-execution state; racing one instance
                # from two queries is never worth the risk)
                self.stats["busy_bypass"] += 1
                note(plan_cache="busy_bypass")
                emit("planCache", op="busy", norm=sig.norm[:12])
                return None
            self.stats["hits"] += 1
            note(plan_cache="hit")
            emit("planCache", op="hit", norm=sig.norm[:12])
            return PlanLease(variant, "hit")

    def insert(self, conf_digest: str, sig, fingerprints,
               plan: Exec) -> Optional[PlanLease]:
        """Caches a freshly-planned physical plan and returns it LEASED
        (the caller executes it immediately)."""
        if self.max_plans <= 0 or sig is None:
            return None
        key = (conf_digest, sig.norm)
        variant = _PlanVariant(plan, fingerprints, sig.lit_values, key)
        variant.lock.acquire()
        with self._lock:
            entry = self._entries.setdefault(key, {})
            old = entry.get(sig.lit_values)
            if old is not None:
                self.total_bytes -= old.nbytes
            entry[sig.lit_values] = variant
            self.total_bytes += variant.nbytes
            self._entries.move_to_end(key)
            self.stats["inserts"] += 1
            # evict least-recently-used UNLEASED variants past either
            # bound (variant count OR retained-byte estimate)
            while self._variant_count() > self.max_plans or \
                    (self.max_bytes > 0
                     and self.total_bytes > self.max_bytes):
                evicted = False
                for k in list(self._entries):
                    ent = self._entries[k]
                    for lv, v in list(ent.items()):
                        if v is variant or v.lock.locked():
                            continue
                        del ent[lv]
                        self.total_bytes -= v.nbytes
                        self.stats["evictions"] += 1
                        evicted = True
                        break
                    if not ent and k in self._entries:
                        del self._entries[k]
                    if evicted:
                        break
                if not evicted:
                    break       # everything live is leased: over-budget
        emit("planCache", op="insert", norm=sig.norm[:12])
        return PlanLease(variant, "insert")

    def discard(self, lease: PlanLease) -> None:
        """Drops the leased variant from the cache AND releases the
        lease.  Called when an execution of the variant's plan FAILED:
        exec instances memoize per-execution state (exchange stores,
        join build caches) that a half-run — e.g. a speculative pass
        that died before its overflow check — may have left poisoned,
        so the instance must never be handed to a later exact hit."""
        v = lease._variant
        if v is None:
            return
        with self._lock:
            entry = self._entries.get(v.key)
            if entry is not None and entry.get(v.lit_values) is v:
                del entry[v.lit_values]
                self.total_bytes -= v.nbytes
                if not entry:
                    del self._entries[v.key]
                self.stats["invalidations"] += 1
                emit("planCache", op="discard",
                     norm=v.key[1][:12] if v.key else "")
        lease.release()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.total_bytes = 0


class _ResultEntry:
    __slots__ = ("batch", "spill_path", "nbytes", "fingerprints", "pins")

    def __init__(self, batch, nbytes: int, fingerprints, pins=()):
        self.batch = batch            # HostColumnarBatch | None (spilled)
        self.spill_path: Optional[str] = None
        self.nbytes = nbytes
        self.fingerprints = fingerprints
        #: strong refs to the objects the key's signature identifies by
        #: id() (in-memory scan device caches) — keeps a recycled address
        #: from colliding with a live entry (signature.plan_pins)
        self.pins = pins


class ResultCache:
    """Deterministic (exact plan signature, conf) -> result batches,
    spillable under pressure, invalidated on file change."""

    def __init__(self, max_bytes: int = 256 << 20, spill: bool = True,
                 spill_dir: Optional[str] = None):
        self.max_bytes = int(max_bytes)
        self.spill_enabled = bool(spill)
        self._spill_dir = spill_dir
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _ResultEntry]" = \
            collections.OrderedDict()
        self.mem_bytes = 0
        self.disk_bytes = 0
        self.stats = {"hits": 0, "misses": 0, "inserts": 0,
                      "invalidations": 0, "spills": 0, "unspills": 0,
                      "evictions": 0}

    def _ensure_spill_dir(self) -> str:
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="srt-result-cache-")
        return self._spill_dir

    # -- arrow IPC spill tier -----------------------------------------------
    def _write_spill(self, key: str, batch) -> str:
        """Serializes one batch to the arrow tier — called OUTSIDE the
        cache lock (the write is the expensive part; peers keep
        hitting).  Uses the shuffle serializer's codec frame with the
        catalog's spill codec (``spark.rapids.memory.spill.codec``), so
        result-cache spill files compress through the same lz4/zlib
        path every other host->disk spill does."""
        from spark_rapids_tpu.memory import catalog as CAT
        from spark_rapids_tpu.shuffle.serializer import serialize_batch
        path = os.path.join(self._ensure_spill_dir(), f"{key}.arrow")
        frame = serialize_batch(batch, CAT.SPILL_CODEC)
        with open(path, "wb") as fh:
            fh.write(frame)
        return path

    def _spill_victims(self, victims) -> None:
        """(key, entry, batch snapshot) list from ``_collect_victims``:
        serialize each outside the lock, then COMMIT (or discard, if the
        entry was dropped/invalidated meanwhile) under it."""
        for key, e, batch in victims:
            try:
                path = self._write_spill(key, batch)
            except OSError:
                continue        # disk trouble: entry simply stays in memory
            committed = False
            with self._lock:
                if self._entries.get(key) is e and e.batch is not None:
                    e.spill_path = path
                    e.batch = None
                    self.mem_bytes -= e.nbytes
                    self.disk_bytes += e.nbytes
                    self.stats["spills"] += 1
                    committed = True
            if committed:
                emit("resultCache", op="spill", bytes=e.nbytes)
            else:
                try:
                    os.remove(path)
                except OSError:
                    pass

    def _load(self, path: str):
        from spark_rapids_tpu.shuffle.serializer import deserialize_batch
        with open(path, "rb") as f:
            return deserialize_batch(f.read())

    def _drop(self, key: str, e: _ResultEntry) -> None:
        if e.batch is not None:
            self.mem_bytes -= e.nbytes
        if e.spill_path:
            self.disk_bytes -= e.nbytes
            try:
                os.remove(e.spill_path)
            except OSError:
                pass
        self._entries.pop(key, None)

    # -- public --------------------------------------------------------------
    def lookup(self, key: Optional[str], fingerprints):
        """Cached HostColumnarBatch or None; a fingerprint mismatch
        deletes the entry (file changed) and misses."""
        if key is None or self.max_bytes <= 0:
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.stats["misses"] += 1
                return None
            if e.fingerprints != fingerprints:
                self._drop(key, e)
                self.stats["invalidations"] += 1
                self.stats["misses"] += 1
                emit("resultCache", op="invalidate", key=key[:12])
                return None
            self._entries.move_to_end(key)
            self.stats["hits"] += 1
            emit("resultCache", op="hit", key=key[:12])
            if e.batch is not None:
                return e.batch
            path = e.spill_path     # snapshot under the lock: a peer's
            # re-admission nulls it after we release
        # disk load outside the lock (IO under a hot lock stalls peers)
        try:
            if path is None:
                raise OSError("spill path gone")
            batch = self._load(path)
        except OSError:
            # raced a concurrent unspill-re-admission (serve its batch)
            # or a drop/rebalance/invalidate that unlinked the file (a
            # lost entry is a MISS) — never a query failure
            with self._lock:
                if self._entries.get(key) is e and e.batch is not None:
                    return e.batch
                self.stats["hits"] -= 1
                self.stats["misses"] += 1
            return None
        drop_path = None
        with self._lock:
            self.stats["unspills"] += 1
            # re-admit a hot entry while the budget has room, or every
            # hit of this key keeps paying the disk read
            if self._entries.get(key) is e and e.batch is None and \
                    self.mem_bytes + e.nbytes <= self.max_bytes:
                e.batch = batch
                self.mem_bytes += e.nbytes
                self.disk_bytes -= e.nbytes
                drop_path, e.spill_path = e.spill_path, None
        if drop_path:
            try:
                os.remove(drop_path)
            except OSError:
                pass
        return batch

    def put(self, key: Optional[str], fingerprints, batch,
            pins=()) -> bool:
        if key is None or self.max_bytes <= 0 or batch is None:
            return False
        nbytes = int(batch.nbytes())
        if nbytes > self.max_bytes:
            return False        # a single oversized result never caches
        with self._lock:
            old = self._entries.get(key)
            if old is not None:
                self._drop(key, old)
            e = _ResultEntry(batch, nbytes, fingerprints, pins)
            self._entries[key] = e
            self.mem_bytes += nbytes
            self.stats["inserts"] += 1
            victims = self._collect_victims()
        emit("resultCache", op="insert", key=key[:12], bytes=nbytes)
        self._spill_victims(victims)
        return True

    def _collect_victims(self):
        """Under memory pressure (caller holds ``_lock``): hard-evict
        what cannot spill, and return the LRU entries TO spill —
        serialization and the disk write happen outside the lock
        (``_spill_victims``), so concurrent lookups keep hitting the
        still-in-memory batches meanwhile."""
        victims = []
        pending = 0         # bytes leaving memory once the spills commit
        for key in list(self._entries):
            if self.mem_bytes - pending <= self.max_bytes:
                break
            e = self._entries[key]
            if e.batch is None:
                continue
            if self.spill_enabled and self.disk_bytes + pending + \
                    e.nbytes <= 4 * self.max_bytes:
                victims.append((key, e, e.batch))
                pending += e.nbytes
            else:
                self._drop(key, e)
                self.stats["evictions"] += 1
        return victims

    def resize(self, max_bytes: int) -> None:
        """Online budget change (``QueryServer.set_conf``): applies
        immediately — shrinking spills/evicts LRU entries down to the
        new bound before returning."""
        with self._lock:
            self.max_bytes = int(max_bytes)
            if self.max_bytes <= 0:
                for key in list(self._entries):
                    self._drop(key, self._entries[key])
                victims = []
            else:
                victims = self._collect_victims()
        self._spill_victims(victims)

    def invalidate_files(self, paths) -> int:
        """Catalog hook: drops every entry whose fingerprints touch any
        of ``paths`` (e.g. an overwrite the server itself performed)."""
        paths = {str(p) for p in paths}
        dropped = 0
        with self._lock:
            for key in list(self._entries):
                e = self._entries[key]
                if any(fp[0] in paths for fp in e.fingerprints):
                    self._drop(key, e)
                    dropped += 1
        if dropped:
            self.stats["invalidations"] += dropped
            emit("resultCache", op="invalidate", files=len(paths),
                 dropped=dropped)
        return dropped

    def clear(self) -> None:
        with self._lock:
            for key in list(self._entries):
                self._drop(key, self._entries[key])
