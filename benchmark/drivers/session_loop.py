"""One session, queries in sequence: ``TpuSession.sql(text).collect()``
(the Power Test's shape).  The window closes when the query in flight at the
deadline has returned, so the rate is taken over whole queries and all of
the time they took."""

from __future__ import annotations

import time

import jax


class Driver:
    streams = 1

    def __init__(self, session, cell: dict):
        self.session = session

    def explain(self, text: str) -> str:
        return self.session.sql(text).explain()

    def warm(self, text: str):
        return self.session.sql(text).collect()

    def counters(self) -> dict:
        return {}

    def run_one(self, text: str) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.plan"):
            df = self.session.sql(text)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.collect"):
            rows = df.collect()
        t2 = time.perf_counter()
        return {"rows": rows, "plan_s": t1 - t0, "latency_s": t2 - t0}

    def close(self):
        pass
