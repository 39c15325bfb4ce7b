"""device layer: mean time of a query of the window in which a task of it
waited for the device's permit (``spark.rapids.sql.concurrentGpuTasks``)
and no span inside that wait was open: ``phases["device.permit"]``, which
``TpuSemaphore.acquire_if_necessary`` opens only where a task waits, so a
query whose tasks never waited counts 0.  Whether the program runs its
tasks under spans at all is read off ``task.run``, which every query of
such a program carries: one without it (the parent of the PR that added
it) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    def waited_ms(s):
        phases = s["phases"]
        if "task.run" not in phases:        # a program without the spans
            raise KeyError("task.run")
        return 1e3 * phases.get("device.permit", 0.0)
    return mean_per_query(run, waited_ms)
