"""operators layer: columns the device joins did not gather, a query of
the window: the mean of the summaries' ``join_cols_pruned``.  For each
output batch of a join, the columns its two children delivered that no
consumer above reads (keys and filter-only columns), which the join reads
from its children and does not write at the pair bucket.  A program that
does not count it (the parent of the PR that added the counter) leaves
the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["join_cols_pruned"])
