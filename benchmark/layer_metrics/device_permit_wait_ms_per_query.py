"""device layer: mean time a served query of the window waited for the
device's permit (``spark.rapids.sql.concurrentGpuTasks``): its
``device.permit`` spans, which ``TpuSemaphore.acquire_if_necessary``
opens only where a task waits, so a query that never waited counts 0.
Whether the program has the span at all is read off ``serve.admit``,
which every served query of such a program carries: one without it (the
parent of the PR that added both) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    def waited_ms(s):
        phases = s["phases"]
        if "serve.admit" not in phases:     # a program without the spans
            raise KeyError("serve.admit")
        return 1e3 * phases.get("device.permit", 0.0)
    return mean_per_query(run, waited_ms)
