"""operators layer: rows a query's grouping-set fan-out (ROLLUP, CUBE,
GROUPING SETS: ``TpuExpandExec``) handed to the aggregation above it,
padding included, a query of the window: the mean of the summaries'
``expand_rows_padded``.  A query without grouping sets counts 0.  A
program that does not count it (the parent of the PR that added the
counter) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["expand_rows_padded"])
