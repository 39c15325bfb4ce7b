"""Optimistic device-side sizing with replay-on-overflow.

The static-shape discipline needs a host-known bucket for every padded
output, but fetching an exact size costs a host round trip per fetch —
one device sync per JOIN.  This module lets an
operator GUESS a bucket from static information (e.g. join pair table =
probe bucket: exact for the FK->PK joins that dominate star schemas),
record a 0-d device overflow flag, and defer the truth test to the one
sync the query already pays at collect.  If any flag fired, the action
replays with speculation disabled (exact, sync-per-join sizing).

Who still speculates: a hash join whose probe batch has a bucket at or
under ``columnar/column.SIZED_MIN_BUCKET`` (32,768 rows).  There the guess
costs little (the padding of a small bucket is cheap on the device) and
the round trip would be most of the join.  Above the floor the balance is
the other way round — on the chip a scalar fetch costs milliseconds and a
fact table's padding, carried through every operator above the join,
seconds (PERF.md section 6, PR 34) — so a large probe fetches its
candidate total, registers no flag, and can never force a replay.

Reference analog: the retry-OOM framework (RmmRapidsRetryIterator.scala)
re-executes work when a resource guess was wrong; here the guessed
resource is an output shape instead of memory.
"""

from __future__ import annotations

import contextvars
import threading
from typing import List

_LOCK = threading.Lock()
#: active context stack — a contextvar, so concurrent collects on
#: different threads never see each other's contexts.  Partition tasks on
#: the pool run inside a COPY of the submitting thread's context
#: (plan/base.py iter_partition_tasks), which routes their overflow flags
#: to the right collect.
_STACK: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "speculation_stack", default=())
#: replay mode: operators must size exactly (same contextvar propagation)
_DISABLED: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "speculation_disabled", default=0)


class SpeculationOverflow(Exception):
    """A speculative bucket was too small; the action must replay."""


class SpeculationContext:
    def __init__(self):
        self._flags = []
        self._lock = threading.Lock()

    def add(self, flag) -> None:
        """Registers a 0-d bool device array: True = overflow."""
        with self._lock:
            self._flags.append(flag)

    def check(self) -> None:
        """ONE device sync over every flag; raises on any overflow."""
        with self._lock:
            flags, self._flags = self._flags, []
        if not flags:
            return
        import numpy as np
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        # flags produced by shard-local pipelines are committed to
        # DIFFERENT devices under a mesh — they cannot meet in one
        # stack; group per device so the sync count stays one per
        # device, not one per flag
        by_dev: dict = {}
        for f in flags:
            devices = getattr(f, "devices", None)
            key = None
            if callable(devices):
                try:
                    key = tuple(sorted(d.id for d in devices()))
                except Exception:  # noqa: BLE001 - placement probe only
                    key = None
            by_dev.setdefault(key, []).append(f)
        from spark_rapids_tpu.aux import transitions as TR
        if any(bool(TR.fetch(jnp.any(jnp.stack(group)),
                             site="speculation-overflow"))
               for group in by_dev.values()):
            raise SpeculationOverflow()


def active() -> "SpeculationContext | None":
    if _DISABLED.get():
        return None
    stack = _STACK.get()
    return stack[-1] if stack else None


class speculation_scope:
    """``with speculation_scope() as ctx:`` — ctx is None in replay mode."""

    def __enter__(self):
        if _DISABLED.get():
            self._ctx = None
            self._token = None
            return None
        self._ctx = SpeculationContext()
        self._token = _STACK.set(_STACK.get() + (self._ctx,))
        return self._ctx

    def __exit__(self, *exc):
        if self._token is not None:
            _STACK.reset(self._token)
        return False


class no_speculation:
    """Replay mode: every operator sizes exactly (sync-per-decision)."""

    def __enter__(self):
        self._token = _DISABLED.set(_DISABLED.get() + 1)

    def __exit__(self, *exc):
        _DISABLED.reset(self._token)
        return False
