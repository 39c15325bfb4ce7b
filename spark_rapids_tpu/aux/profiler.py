"""The profiler driver.

Reference: the built-in CUPTI profiler (profiler.scala:37,315
ProfilerOnExecutor/Driver) writing trace files to a path, scoped by
job/time ranges — here ``jax.profiler.start_trace`` (xprof) driven by the
same shape.  What the trace holds of the program's own host work is
written by ``aux.tracing.span`` (``srt.*`` annotations, always on: a
TraceMe is inert while no trace runs)."""

from __future__ import annotations

import contextlib
import os


class Profiler:
    """Executor-side profiler driver (reference: ProfilerOnExecutor) —
    starts/stops an xprof trace into ``path``; ``profile(df_action)`` is
    the scoped form the reference drives via job/stage ranges."""

    def __init__(self, path: str):
        self.path = path
        self._active = False

    def start(self) -> None:
        if self._active:
            return
        os.makedirs(self.path, exist_ok=True)
        import jax.profiler
        jax.profiler.start_trace(self.path)
        self._active = True

    def stop(self) -> None:
        if not self._active:
            return
        import jax.profiler
        jax.profiler.stop_trace()
        self._active = False

    @contextlib.contextmanager
    def scoped(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()
