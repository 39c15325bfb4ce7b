"""compile layer: steady-path calls of already-built stage programs per
query (``StageProgram.__call__``), from the per-query summary."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["dispatches"])
