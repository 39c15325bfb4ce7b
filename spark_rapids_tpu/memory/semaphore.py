"""Device admission semaphore (reference: GpuSemaphore.scala:51-120).

Limits how many tasks may hold the device concurrently
(``spark.rapids.sql.concurrentGpuTasks``).  Tasks acquire before their first
device section and release at completion; re-entrant per task.  Holders can
be dumped for debugging (reference: dumpActiveStackTracesToLog :120).

Built on a condition variable (not a raw ``threading.Semaphore``) so waits
are INTERRUPTIBLE: a waiter polls the resource arbiter between bounded wait
slices, marking itself BLOCKED_ON_SEMAPHORE in the task thread-state
registry (``memory/arbiter.py``) and honoring watchdog cancellation — the
pre-arbiter semaphore waited forever with no escalation, exactly the hang
the hung-query watchdog exists to break.  Acquire/release also keep the
arbiter's device-holder view current, which is what the deadlock detector's
"all device-holding tasks are blocked" condition reads.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Dict, Optional

from spark_rapids_tpu.aux.tracing import span

#: wait slice between cancellation checks while queued on admission
_WAIT_SLICE_S = 0.05


class TpuSemaphore:
    def __init__(self, max_concurrent: int):
        self.max_concurrent = max_concurrent
        self._permits = max_concurrent
        from spark_rapids_tpu.aux.lockorder import tracked_condition
        self._cond = tracked_condition("semaphore")
        self._holders: Dict[int, dict] = {}
        self._waiting = 0

    @staticmethod
    def _tid(task_id: Optional[int]) -> int:
        from spark_rapids_tpu.memory.retry import task_context
        if task_id is not None:
            return task_id
        ctx_id = task_context().task_id
        return ctx_id if ctx_id is not None else threading.get_ident()

    def acquire_if_necessary(self, task_id: Optional[int] = None) -> None:
        """Idempotent per-task acquire (reference: acquireIfNecessary :100)."""
        from spark_rapids_tpu.memory.arbiter import TaskState, get_arbiter
        from spark_rapids_tpu.memory.retry import task_context
        tid = self._tid(task_id)
        arb = get_arbiter()
        with self._cond:
            entry = self._holders.get(tid)
            if entry is not None:
                entry["depth"] += 1
                return
            def no_permit() -> bool:
                # another thread of the SAME task acquiring concurrently
                # creates the holder entry; re-check it each wake so both
                # land on one permit at depth 2 (the old duplicate-permit
                # return dance, folded into the wait condition)
                return tid not in self._holders and self._permits <= 0

            t0 = None
            if no_permit():
                # the span opens only where the task waits: its query's
                # ``device.permit`` seconds are the wait and nothing else
                self._waiting += 1
                try:
                    with span("device.permit", task_id=tid):
                        t0 = arb.wait_cancellable(
                            self._cond, no_permit,
                            TaskState.BLOCKED_ON_SEMAPHORE,
                            slice_s=_WAIT_SLICE_S)
                finally:
                    self._waiting -= 1
            entry = self._holders.get(tid)
            if entry is not None:
                # a sibling thread of the same task won the race and
                # created the holder entry: share its permit (depth 2),
                # but the wait this thread endured still counts below
                entry["depth"] += 1
                raced = True
            else:
                raced = False
                self._permits -= 1
                self._holders[tid] = {
                    "depth": 1, "since": time.monotonic(),
                    "thread": threading.current_thread().name,
                    "ident": threading.get_ident()}
        if not raced:
            arb.note_device_held(tid, True)
        wait = time.monotonic() - t0 if t0 is not None else 0.0
        mt = task_context().metrics
        if mt is not None:
            mt.semaphore_wait_seconds += wait
        from spark_rapids_tpu.aux.events import emit
        emit("semaphoreAcquired", task_id=tid, wait_s=round(wait, 6))

    def release_if_necessary(self, task_id: Optional[int] = None) -> None:
        tid = self._tid(task_id)
        with self._cond:
            entry = self._holders.get(tid)
            if entry is None:
                return
            entry["depth"] -= 1
            if entry["depth"] > 0:
                return
            del self._holders[tid]
            self._permits += 1
            self._cond.notify_all()
        from spark_rapids_tpu.memory.arbiter import get_arbiter
        get_arbiter().note_device_held(tid, False)

    def release_all(self, task_id: Optional[int] = None) -> None:
        """Drops the task's hold entirely regardless of depth (task
        completion listener analog — reference: GpuSemaphore completeTask)."""
        tid = self._tid(task_id)
        with self._cond:
            if self._holders.pop(tid, None) is None:
                return
            self._permits += 1
            self._cond.notify_all()
        from spark_rapids_tpu.memory.arbiter import get_arbiter
        get_arbiter().note_device_held(tid, False)

    def held_by(self, task_id: int) -> bool:
        with self._cond:
            return task_id in self._holders

    def resize(self, new_max: int) -> int:
        """Online permit-budget adjustment (the serving AutoTuner loop
        applies ``spark.rapids.sql.concurrentGpuTasks`` deltas between
        queries).  Growing wakes waiters immediately; shrinking lets
        permits go transiently negative and takes effect as holders
        release — a held permit is never revoked.  Returns the old
        budget."""
        new_max = max(1, int(new_max))
        with self._cond:
            old = self.max_concurrent
            if new_max == old:
                return old
            self._permits += new_max - old
            self.max_concurrent = new_max
            if new_max > old:
                self._cond.notify_all()
        return old

    def stats(self) -> dict:
        """Read-only snapshot for the resource sampler: permit budget,
        current holders and threads queued on admission."""
        with self._cond:
            return {"max_concurrent": self.max_concurrent,
                    "holders": len(self._holders),
                    "waiting": self._waiting}

    def dump_active_holders(self) -> str:
        """reference: GpuSemaphore.dumpActiveStackTracesToLog — each
        holder's LIVE stack (via sys._current_frames, keyed by the
        ident recorded at acquire), not the dumper's own stack."""
        frames = sys._current_frames()
        now = time.monotonic()
        with self._cond:
            holders = [(tid, dict(e)) for tid, e in self._holders.items()]
            waiting = self._waiting
        lines = [f"== semaphore: {len(holders)}/{self.max_concurrent} "
                 f"permit(s) held, {waiting} waiting =="]
        for tid, e in holders:
            held = now - e["since"]
            lines.append(f"task {tid} thread={e['thread']} "
                         f"held={held:.1f}s depth={e['depth']}")
            f = frames.get(e.get("ident"))
            if f is not None:
                for fl in traceback.format_stack(f)[-4:]:
                    lines.extend("  " + x
                                 for x in fl.rstrip().splitlines())
        return "\n".join(lines)
