"""operators layer: build sides of broadcast hash joins that a query of the
window pulled, concatenated and keyed: the mean of the summaries'
``broadcast_builds``.  A broadcast join builds once a query whatever the
number of probe tasks, so a q3 reads 2; a build repeated by every task
would read 2 x partitions.  A program that does not count it (the parent
of the PR that added the counter) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["broadcast_builds"])
