"""One ``QueryServer`` on one session, ``streams`` closed-loop clients:
each submits its next query when its last has returned
(``QueryServer.submit(text).result()``, the Throughput Test's shape)."""

from __future__ import annotations

import time

import jax


class Driver:
    def __init__(self, session, cell: dict):
        from spark_rapids_tpu.serving.server import QueryServer
        self.streams = int(cell["streams"])
        self.timeout_s = float(cell.get("result_timeout_s", 300))
        self.session = session
        self.server = QueryServer(session=session)

    def explain(self, text: str) -> str:
        return self.session.sql(text).explain()

    def warm(self, text: str):
        return self.server.submit(text, tag="warm").result(self.timeout_s)

    def counters(self) -> dict:
        st = self.server.stats()
        keep = {}
        for name in ("plan_cache", "result_cache"):
            if isinstance(st.get(name), dict):
                keep[name] = {k: v for k, v in st[name].items()
                              if isinstance(v, (int, float))}
        return keep

    def run_one(self, text: str) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            sub = self.server.submit(text)
        with jax.profiler.TraceAnnotation("bench.collect"):
            rows = sub.result(self.timeout_s)
        t1 = time.perf_counter()
        stages = dict(sub.info.get("stages") or {})
        plan_s = None
        if stages:
            plan_s = stages.get("lookup_s", 0.0) + stages.get("plan_s", 0.0)
        return {"rows": rows, "plan_s": plan_s, "latency_s": t1 - t0,
                "stages": stages, "resolved": sub.info.get("resolved")}

    def close(self):
        self.server.stop()
