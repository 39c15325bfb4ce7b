"""parse/plan layer: mean host time per query inside ``TpuOverrides.apply``
(pruning, fusion, literal promotion, distribution, pipelining,
instrumentation), from the program's ``plan.rewrite`` spans.  A replayed
query rewrites twice, and both count."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: 1e3 * s["phases"]["plan.rewrite"])
